"""A cell of ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by name: ``configs/<config>.json`` (sizes) with its
reference ``configs/<config>.py``, ``traffic/<traffic>.json``,
``metrics/<metric>.py`` and ``limits/<cell>.json`` (the correctness limits
of one cell).  Adding a cell, a mix, a configuration or a metric adds files
and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list
    limits: dict
    base: pathlib.Path

    @property
    def reference(self) -> pathlib.Path:
        return self.base / "configs" / f"{self.config_name}.py"

    def metric_reader(self, name: str) -> pathlib.Path:
        return self.base / "metrics" / f"{name}.py"


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(bench_path: pathlib.Path, root: pathlib.Path, workload: str,
         base: pathlib.Path = HERE) -> Cell:
    """The cell ``workload``: files are looked up under ``base`` (the
    benchmark's directory), the configuration at its ``file`` under
    ``root``."""
    bench = json.loads(pathlib.Path(bench_path).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((base / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((base / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(workload, w["config"], w["traffic"], w["chips"], config, mix,
                e2e, per_layer, limits["limits"], base)

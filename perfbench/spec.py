"""Finding a cell's files by the names in ``BENCHMARK.json``.

    perfbench/configs/<config>.json   the configuration as it is run
    perfbench/configs/<config>.py     how the program and the reference
                                      are built from it
    perfbench/traffic/<traffic>.json  the traffic mix
    perfbench/limits/<workload>.json  the cell's limits for ``correct``
    perfbench/kinds/<kind>.py         the driver of a kind of traffic
    perfbench/metrics/<metric>.py     the reader of one metric

A later cell, traffic mix or metric is added by adding such files and an
entry in ``BENCHMARK.json``; no file here names one.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry with everything found by its names."""

    def __init__(self, bench: dict, name: str):
        found = [w for w in bench["workloads"] if w["name"] == name]
        if not found:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.entry = found[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = [c for c in bench["configs"]
               if c["name"] == self.entry["config"]][0]
        self.config_entry = cfg
        self.config = json.loads((ROOT / cfg["file"]).read_text())
        self.config_module = load_module(
            HERE / "configs" / f"{cfg['name']}.py",
            "perfbench_config_" + _ident(cfg["name"]))
        self.traffic = json.loads(
            (HERE / "traffic" / f"{self.entry['traffic']}.json").read_text())
        self.end_to_end = _metrics_of(bench["end_to_end"], name)
        self.per_layer = _metrics_of(bench["per_layer"], name)

    @functools.cached_property
    def limits(self) -> dict:
        """The cell's limits file, read when first asked for."""
        return json.loads(
            (HERE / "limits" / f"{self.name}.json").read_text())

    def kind(self) -> ModuleType:
        return load_module(HERE / "kinds" / f"{self.traffic['kind']}.py",
                           "perfbench_kind_" + _ident(self.traffic["kind"]))


def _metrics_of(entries: List[dict], cell: str) -> List[dict]:
    """The metrics a cell reports: those that list it, and those with no
    list of cells."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


def reader(metric: str):
    """The ``read(ctx)`` function of ``perfbench/metrics/<metric>.py``."""
    return load_module(HERE / "metrics" / f"{metric}.py",
                       "perfbench_metric_" + _ident(metric)).read


def _ident(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)

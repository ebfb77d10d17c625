"""Find everything a run needs by the names in ``BENCHMARK.json``.

Nothing here names a cell, a configuration or a metric: a cell's traffic
mix is ``bench/workloads/<cell>.json``, a configuration is
``bench/configs/<config>.json`` with its plain reference beside it in
``bench/configs/<config>.reference.py``, the code that drives a kind of
configuration is ``bench/drivers/<driver>.py`` (the configuration file
names its driver), a traffic generator is ``bench/generators/<kind>.py``
(the traffic file names its generator), and each metric is read by
``bench/metrics/<metric>.py``.  Adding any of them adds files and
entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def load_json(path: Path) -> Any:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: Path) -> ModuleType:
    """Import a benchmark plug-in by file path (its name may hold dots)."""
    if not path.is_file():
        raise SpecError(f"missing plug-in {path}")
    name = "bench_plugin_" + "_".join(path.relative_to(BENCH).with_suffix(
        "").parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` and the lookups a run makes in it."""

    def __init__(self, path: Path = ROOT / "BENCHMARK.json"):
        self.data = load_json(path)

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def metrics_for(self, cell: str, traced: bool) -> List[Dict[str, Any]]:
        """The metrics a run of ``cell`` reports: its end-to-end metrics
        with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
        group = self.data["per_layer" if traced else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]


def config_file(entry: Dict[str, Any]) -> Dict[str, Any]:
    return load_json(ROOT / entry["file"])


def traffic_file(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "workloads" / f"{name}.json")


def reference(name: str) -> ModuleType:
    return load_module(BENCH / "configs" / f"{name}.reference.py")


def driver(name: str) -> ModuleType:
    return load_module(BENCH / "drivers" / f"{name}.py")


def generator(name: str) -> ModuleType:
    return load_module(BENCH / "generators" / f"{name}.py")


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")

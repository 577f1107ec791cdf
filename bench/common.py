"""What the harness, the drivers and the metric readers share.

The harness finds everything by name: the cell in ``BENCHMARK.json``, its
configuration in ``configs/<config>.json``, its traffic mix in
``traffic/<traffic>.json``, the traffic's driver in
``drivers/<driver>.py`` and each per-layer metric's reader in
``metrics/<metric>.py``. A later cell, mix, driver or metric is a new file
and a new entry; no file here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path, once; the file name may hold dots and
    dashes."""
    module = sys.modules.get(name)
    if module is not None and getattr(module, "__file__", None) == path:
        return module
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One workload of the benchmark, resolved to its files."""

    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict
    traffic: Dict
    driver_path: str
    end_to_end: List[Dict]
    per_layer: List[Dict]
    metric_paths: Dict[str, str]

    def driver(self):
        return load_module(self.driver_path,
                           "bench_driver_" + self.traffic["driver"])

    def reader(self, metric: str):
        return load_module(self.metric_paths[metric],
                           "bench_metric_" + metric.replace(".", "_")
                           .replace("-", "_"))


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell_name: str, root: str = ROOT) -> Cell:
    """The cell's entry, configuration, traffic, driver and readers, or an
    error naming what is missing."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json")
    w = cells[cell_name]
    bench_dir = os.path.join(root, "bench")
    config = load_json(
        os.path.join(bench_dir, "configs", w["config"] + ".json"))
    traffic = load_json(
        os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    driver_path = os.path.join(bench_dir, "drivers", traffic["driver"] + ".py")
    if not os.path.exists(driver_path):
        raise FileNotFoundError(driver_path)
    per_layer = [m for m in bench["per_layer"] if applies(m, cell_name)]
    paths = {}
    for m in per_layer:
        p = os.path.join(bench_dir, "metrics", m["name"] + ".py")
        if not os.path.exists(p):
            raise FileNotFoundError(p)
        paths[m["name"]] = p
    return Cell(
        name=cell_name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]), config=config, traffic=traffic,
        driver_path=driver_path,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, cell_name)],
        per_layer=per_layer, metric_paths=paths)


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's seed and window length, and
    whether the window is traced."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool

    @property
    def config(self) -> Dict:
        return self.cell.config

    @property
    def traffic(self) -> Dict:
        return self.cell.traffic


@dataclasses.dataclass
class Check:
    """One compared number: the run is correct when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class WindowResult:
    """What a driver's window returns: its end-to-end metric values, the
    work attempted and failed, and counts for the per-layer readers."""

    metrics: Dict[str, float]
    attempted: int
    failed: int
    counts: Dict[str, Any] = dataclasses.field(default_factory=dict)


def enable_compile_cache() -> str:
    """The program's persistent compilation cache, keeping every program:
    the small ones too, so that a run after the first compiles nothing."""
    import jax
    from repro.common.compile_cache import enable_compile_cache as enable

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return enable()


def span(name: str):
    """A harness span on the profiler's clock (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def log(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)

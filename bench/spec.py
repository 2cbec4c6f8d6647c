"""Resolve a benchmark cell by name into the files that define it.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json    model sizes, as run, beside their source,
                                   and the name of their ``"arch"``
    bench/arch/<arch>.py           the block: program layout, weights,
                                   float32 reference, counts (see
                                   ``bench/arch/dense.py``)
    bench/traffic/<traffic>.json   parameters for ``bench/loadgen.py``
    bench/cells/<workload>.json    the engine settings of one cell
    bench/metrics/<metric>.py      a reader with ``read(run) -> float|None``

So a cell, a configuration, an architecture or a metric is added by adding
files and entries, never by editing a file that is already there. Nothing
here touches a device; loading an architecture imports JAX.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: Optional[str] = None            # per-layer metrics only
    workloads: Optional[tuple] = None      # None: every cell that reports it

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict           # bench/configs/<config>.json
    arch: object           # bench/arch/<config["arch"]>.py, loaded
    traffic: Dict          # bench/traffic/<traffic>.json
    engine: Dict           # bench/cells/<name>.json
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _metric(entry: Dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(name=entry["name"], unit=entry["unit"],
                  moves=entry.get("moves"),
                  workloads=tuple(wl) if wl is not None else None)


def benchmark(root: Path = ROOT) -> Dict:
    return _load_json(root / "BENCHMARK.json")


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration, traffic, engine
    settings and the metrics it reports. Raises KeyError for a name that
    ``BENCHMARK.json`` does not list, FileNotFoundError for a missing file."""
    spec = benchmark(root)
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in spec['workloads']]}")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    bench = root / "bench"
    e2e = [_metric(m) for m in spec["end_to_end"]]
    e2e = [m for m in e2e if m.applies_to(workload)]
    reported = {m.name for m in e2e}
    layer = [_metric(m) for m in spec["per_layer"]]
    layer = [m for m in layer if m.applies_to(workload) and
             m.moves in reported]
    config = _load_json(root / conf["file"])
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                arch=arch(config["arch"], root),
                traffic=_load_json(bench / "traffic" /
                                   f"{entry['traffic']}.json"),
                engine=_load_json(bench / "cells" / f"{workload}.json"),
                end_to_end=e2e, per_layer=layer)


def _load_module(kind: str, name: str, root: Path):
    path = root / "bench" / kind / f"{name}.py"
    mod_name = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(mod_name, path)
    if mod_spec is None or not path.exists():
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = ROOT) -> Callable:
    """``read`` of ``bench/metrics/<metric>.py``: takes the finished run
    and returns the metric's value, or None where it found nothing."""
    return _load_module("metrics", metric, root).read


def arch(name: str, root: Path = ROOT):
    """The module ``bench/arch/<name>.py``: what the harness knows of one
    architecture's block (``bench/arch/dense.py`` lists it)."""
    return _load_module("arch", name, root)


def model_sizes(config: Dict) -> Dict:
    """The configuration's sizes under the harness's names. Each config
    file keeps its source's own key names and maps them in ``keys``; a
    size the source does not give is in ``assumed``."""
    have = {**config, **config.get("assumed", {})}
    return {ours: have[theirs] for ours, theirs in config["keys"].items()}

"""Finding a cell's configuration, traffic mix, metrics and chip peaks by
name.  Nothing here names a cell, a mix or a metric: everything is read
from ``BENCHMARK.json`` and the files it points to, under ``root``.

- a configuration: the file that ``BENCHMARK.json`` names for it;
- a traffic mix: ``bench/mixes/<traffic>.json``;
- a metric: ``bench/metrics/<name>.py``, a module with ``read(run)``
  returning a number, or None where the run has nothing to read;
- chip peaks: ``bench/peaks.json``, keyed by JAX's ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, NamedTuple, Optional

BENCH_FILE = "BENCHMARK.json"


class Metric(NamedTuple):
    name: str
    unit: str
    read: Callable


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(root: pathlib.Path, name: str) -> Callable:
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(root: pathlib.Path, entries: List[dict], cell: str
             ) -> List[Metric]:
    return [Metric(m["name"], m["unit"], load_reader(root, m["name"]))
            for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(root, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, mix and metric readers."""
    root = pathlib.Path(root)
    bench = _load_json(root / BENCH_FILE)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {BENCH_FILE}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    mix = _load_json(root / "bench" / "mixes" / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), config, mix,
                _metrics(root, bench["end_to_end"], name),
                _metrics(root, bench["per_layer"], name))


def peaks_for(root, device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = _load_json(pathlib.Path(root) / "bench" / "peaks.json")
    kinds = table["devices"]
    if device_kind not in kinds:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (known: {sorted(kinds)})")
    return kinds[device_kind]


def read_metrics(metrics: List[Metric], run) -> Dict[str, dict]:
    """Each metric's reading; a reader that finds nothing returns None
    and its metric is left out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value: Optional[float] = m.read(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out

"""Discovery by name: the cell, its configuration, traffic mix and metrics.

``BENCHMARK.json`` names everything; each configuration, traffic mix and
per-layer metric is a file of its own that the harness finds by that name:

    bench/configs/<file named in BENCHMARK.json>
    bench/traffic/<traffic>.json
    bench/metrics/<metric>.py      UNIT, MOVES and read(ctx)

so a later cell, mix, configuration or metric arrives as new files and new
entries, with no edit to a file that is already here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, NamedTuple, Optional

from bench.harness.device import ROOT

BENCH_DIR = ROOT / "bench"


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file's contents
    traffic_name: str
    traffic: dict           # the traffic mix file's contents
    end_to_end: List[dict]  # the cell's end-to-end metric entries
    per_layer: List[dict]   # the cell's per-layer metric entries


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: Optional[dict] = None,
              root: Path = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, root: Path = ROOT) -> ModuleType:
    """The reader module ``bench/metrics/<name>.py`` of a per-layer metric."""
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + "".join(
        ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for attr in ("UNIT", "MOVES", "read"):
        if not hasattr(mod, attr):
            raise AttributeError(f"metric reader {path} lacks {attr}")
    return mod


def read_per_layer(cell: Cell, ctx, root: Path = ROOT) -> Dict[str, dict]:
    """Every per-layer metric of the cell that its reader finds something
    to read for; a reader that returns None is left out of the line."""
    out = {}
    for m in cell.per_layer:
        reader = metric_reader(m["name"], root)
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out

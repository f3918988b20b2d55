"""Where the benchmark's parts live, found by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each is a file of its own:
``bench/configs/<config>.json`` (the path the configuration entry gives),
``bench/traffic/<traffic>.json`` and, for every per-layer metric,
``bench/metrics/<metric>.py``.  What the cell itself fixes (the offered
rate of an open-loop mix, the limit of its correctness check, and the
readings both were set from) is ``bench/cells/<cell>.json``.  Adding a
cell, a mix or a metric adds files and entries; no file here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]      # the checkout
BENCH = ROOT / "bench"


class SpecError(Exception):
    pass


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict                 # the configuration file, as run
    traffic: dict                # the traffic file, with the cell's rate
    settings: dict               # the cell file
    end_to_end: List[dict]       # the cell's end-to-end metric entries
    per_layer: List[dict]        # the cell's per-layer metric entries


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} not found")
    bm = json.loads(path.read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SpecError(f"no cell {name!r} in BENCHMARK.json "
                        f"(cells: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    conf = configs[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    settings = json.loads((root / "bench" / "cells" / f"{name}.json")
                          .read_text())
    traffic.update(settings.get("traffic", {}))
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config, traffic=traffic, settings=settings,
                end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if _applies(m, name)])


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: List[dict], run, root: Path = ROOT
                 ) -> Dict[str, Optional[float]]:
    return {m["name"]: metric_reader(m["name"], root)(run) for m in entries}

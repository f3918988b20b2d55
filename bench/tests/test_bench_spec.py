"""BENCHMARK.json names only files that exist, and keeps the contract's
shapes: names, units, bounds, readers, one limit file per cell."""
import json
import re

import pytest

import _paths  # noqa: F401
from harness import spec

BM = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"] for m in BM["end_to_end"]}
CELLS = {w["name"] for w in BM["workloads"]}


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_entries(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and c["file"].startswith("bench/")
    conf = json.loads((_paths.ROOT / c["file"]).read_text())
    for key in c["reduced"]:
        assert key in conf["published"] and conf[key] != conf["published"][key]
    assert any(w["config"] == c["name"] for w in BM["workloads"])


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_cells_load(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert len(w["why"]) <= 200
    cell = spec.load_cell(w["name"])
    assert cell.traffic["kind"] in ("open_mmpp2", "closed")
    assert cell.settings["mean_logit_gap"] > 0
    if cell.traffic["kind"] == "open_mmpp2":
        assert 0 < cell.traffic["mean_rate"] <= cell.settings["knee_req_per_s"]


@pytest.mark.parametrize("m", BM["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metrics(m):
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metrics_have_readers(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["moves"] in E2E
    assert set(m.get("workloads", CELLS)) <= CELLS
    assert callable(spec.metric_reader(m["name"]))
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"

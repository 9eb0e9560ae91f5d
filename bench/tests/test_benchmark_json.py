"""BENCHMARK.json against the rules the benchmark's format sets: keys,
names and units, bounds, and that every file a cell needs is there."""
import json
import re

import pytest

import tiny
from harness import Bench

SPEC = json.loads((tiny.CHECKOUT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./\-]{1,200}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert len(SPEC["command"]) <= 32 and all(_line(w)
                                              for w in SPEC["command"])
    assert isinstance(SPEC["run_seconds"], int) and \
        1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_check_fits_the_time_limit():
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (SPEC["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_configs():
    assert 1 <= len(SPEC["configs"]) <= 24
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and \
            _line(c["why"])
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        body = json.loads((tiny.CHECKOUT / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_workloads():
    assert 1 <= len(SPEC["workloads"]) <= 24
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(names) // 2)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["config"]) and \
            NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])


def _metrics():
    return SPEC["end_to_end"] + SPEC["per_layer"]


def test_metric_names_units_and_keys():
    names = [m["name"] for m in _metrics()]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in SOURCES_E2E
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in _metrics():
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_setup_s_and_every_cell_reports_enough():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25 and "workloads" not in \
        e2e["setup_s"]
    for w in SPEC["workloads"]:
        cell = w["name"]
        mine = [m for m in SPEC["end_to_end"]
                if cell in m.get("workloads", [cell])]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", [cell])
                   for m in SPEC["per_layer"])


def test_per_layer_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    layers = {}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        reporting = set(moved.get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reporting
        base = m["name"].split(".")[0]
        layers.setdefault(base, m["layer"])
        assert layers[base] == m["layer"]


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(cell):
    bench = Bench(tiny.CHECKOUT)
    c = bench.cell(cell)
    assert c.config["family"] and c.policy["policy"]
    assert set(c.limits["checks"]) >= {"x0_rel_l2", "window_compiles",
                                       "failed"}
    bench.module("flops", c.config["family"])
    bench.backbone(c.config["family"])
    for m in bench.metrics_for(cell, "end_to_end") + \
            bench.metrics_for(cell, "per_layer"):
        assert callable(bench.reader(m["name"]))


def test_peaks_keyed_by_device_kind():
    bench = Bench(tiny.CHECKOUT)
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        bench.peaks("TPU v9000")

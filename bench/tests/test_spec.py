"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
has the file the harness finds it by."""
from __future__ import annotations

import json
import re

import pytest

from bench.tests.tiny import REPO

SPEC_PATH = REPO / "BENCHMARK.json"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    assert SPEC_PATH.stat().st_size <= 64 * 1024
    return json.loads(SPEC_PATH.read_text())


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"] and spec["paths"] == ["bench"]
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51


def test_configs(spec):
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"]) and c["file"].startswith("bench/")
        assert json.loads((REPO / c["file"]).read_text())["name"] == c["name"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])


def test_cells(spec):
    configs = {c["name"] for c in spec["configs"]}
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        mix = json.loads((REPO / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (REPO / "bench" / "drivers" / f"{mix['driver']}.py").is_file()
    assert len({w["name"] for w in spec["workloads"]}) == len(spec["workloads"])


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    names = set(e2e)
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e and m["name"] not in names
        names.add(m["name"])
        assert (REPO / "bench" / "layer_metrics" / f"{m['name']}.py").is_file()
        for cell in m.get("workloads", cells):
            assert cell in cells and _reports(e2e[m["moves"]], cell)
    for cell in cells:
        reported = [m["name"] for m in spec["end_to_end"] if _reports(m, cell)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(_reports(m, cell) for m in spec["per_layer"])


def test_layers_are_named_alike(spec):
    by_layer = {}
    for m in spec["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_four_chip_cells_at_most_half(spec):
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(len(spec["workloads"]) // 2, 1)

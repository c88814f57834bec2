"""The per-layer readers of the program's stage histograms: numbers on the
tiny closed cell, their parts within the dispatch they split, and nothing
(no error) from a program that has no stage histograms."""
from __future__ import annotations

import json

import pytest

from bench import harness, run
from bench.tests import tiny

READERS = ("loop_ms.closed", "handoff_ms.closed", "engine_host_ms.closed",
           "device_wait_ms.closed")
CELL = "citeseer.equal.closed"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def traced(root):
    import contextlib
    import io

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "2",
                         "--trace", "1"], root=root, chip_check=tiny.cpu_devices) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_a_number_on_the_tiny_cell(traced, name):
    assert traced["correct"] is True
    assert traced["metrics"][name]["unit"] == "ms"
    assert traced["metrics"][name]["value"] > 0


def test_parts_fall_within_the_dispatch(traced):
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    parts = m["handoff_ms.closed"] + m["engine_host_ms.closed"] + m["device_wait_ms.closed"]
    assert parts <= m["dispatch_ms.closed"] * 1.001
    assert parts >= 0.8 * m["dispatch_ms.closed"], (parts, m["dispatch_ms.closed"])


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_stage_histograms(name):
    import importlib.util

    path = harness.BENCH_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    older = {"daemon_dispatch_ms": {"type": "histogram", "values": {"": {"sum": 1.0, "count": 1}}}}
    assert mod.read(harness.Run(counters={})) is None
    assert mod.read(harness.Run(counters=older)) is None

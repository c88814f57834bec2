"""The ``fused_share.closed`` reader: 100% on the tiny closed cell, whose
store is narrow enough for the fused program; the share of fused batches
among every path's; nothing (no error) from a program without the
``engine_device_batches_total`` family."""
from __future__ import annotations

import contextlib
import importlib.util
import io
import json

import pytest

from bench import harness, run
from bench.tests import tiny

NAME = "fused_share.closed"
CELL = "citeseer.equal.closed"


def _reader():
    path = harness.BENCH_DIR / "layer_metrics" / f"{NAME}.py"
    spec = importlib.util.spec_from_file_location("reader_fused_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", str(2**31 + 13), "--seconds", "2",
                         "--trace", "1"], root=root, chip_check=tiny.cpu_devices) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_every_device_batch_of_the_tiny_cell_is_fused(traced):
    assert traced["correct"] is True
    assert traced["metrics"][NAME] == {"value": 100.0, "unit": "%"}


@pytest.mark.parametrize("values,share", [
    ({"path=fused": 3, "path=tiered": 1}, 75.0),
    ({"path=tiered": 4}, 0.0),
    ({"path=fused": 2}, 100.0),
])
def test_share_is_fused_over_every_path(values, share):
    counters = {"engine_device_batches_total": {"type": "counter", "values": values}}
    assert _reader().read(harness.Run(counters=counters)) == share


@pytest.mark.parametrize("counters", [
    {},
    {"engine_queries_total": {"type": "counter", "values": {"": 10}}},
    {"engine_device_batches_total": {"type": "counter", "values": {}}},
])
def test_reader_is_silent_without_device_batches(counters):
    assert _reader().read(harness.Run(counters=counters)) is None

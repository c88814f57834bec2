"""Every cell driven end to end on the CPU at a tiny size, its line read back."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, run
from bench.tests import tiny


def _line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", tiny.cells())
def test_cell_runs_correct(root, workload, trace, capsys):
    spec = harness.load_spec(root)
    assert run.main(["--workload", workload, "--seed", str(2**31 + 7), "--seconds", "1",
                     "--trace", str(trace)], root=root, chip_check=tiny.cpu_devices) == 0
    line = _line(capsys)
    assert line["correct"] is True and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"]) and "breakdown" in line
        layer = {m["name"] for m in spec["per_layer"] if workload in m["workloads"]}
        assert set(line["metrics"]) <= layer
    else:
        e2e = {m["name"] for m in spec["end_to_end"]
               if "workloads" not in m or workload in m["workloads"]}
        assert set(line["metrics"]) == e2e
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("workload", tiny.cells())
def test_same_seed_same_inputs(root, workload):
    import numpy as np

    from bench.drivers import serve
    from bench.yardstick import mix, reach

    cell = harness.load_cell(root, workload)
    a = serve.graph_edges(cell.config, 5)
    b = serve.graph_edges(cell.config, 5)
    assert all((x == y).all() for x, y in zip(a[1:], b[1:]))
    adj = reach.Adjacency(*a)

    def sent(seed):
        _, pairs_of, warm = mix.requests(cell.traffic, adj, 1.0, np.random.default_rng([seed, 1]), seed)
        if "clients" in cell.traffic:   # the first requests each caller sends
            pop = mix.Popularity(cell.traffic, 8192 // cell.traffic["request_pairs"],
                                 cell.traffic["clients"], seed)
            return np.stack([pairs_of(pop.block(c, k)) for c in range(cell.traffic["clients"])
                             for k in range(16)])
        return np.stack([pairs_of(k) for k in range(len(warm))])

    assert np.array_equal(sent(2**31 + 9), sent(2**31 + 9))
    assert not np.array_equal(sent(2**31 + 9), sent(3))


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", "--workload", tiny.cells()[0],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result_line():
    res = _run_cli(tiny.REPO)
    assert res.returncode != 0 and res.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_cli(tmp_path)
    assert res.returncode != 0 and res.stdout.strip() == ""

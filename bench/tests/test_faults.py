"""The check has to fail: the control, and the timed path broken underneath.

Each test skips the look for a chip and drives the rest of a run at a tiny
size on the CPU.  The control breaks a guarantee the configuration states
(hop ids cut to a few bits).  The faults break the program where its
answer is produced: an answer altered, half of a batch's answers left
out.  The serving path keeps no state that a step carries on, and one chip
has no exchange between chips, so those faults do not apply to these
cells.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import run
from bench.tests import tiny

SERVING = tiny.cells()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _correct(root, workload, capsys, variant=None) -> bool:
    run.main(["--workload", workload, "--seed", "12345678901", "--seconds", "1", "--trace", "0"],
             root=root, chip_check=tiny.cpu_devices, variant=variant)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return line["correct"]


@pytest.mark.parametrize("workload", SERVING)
def test_control_is_not_correct(root, workload, capsys):
    assert _correct(root, workload, capsys) is True
    assert _correct(root, workload, capsys, variant="control") is False


def _flip_first(monkeypatch):
    from repro.serve import engine

    real = engine.QueryEngine._device_batch

    def altered(self, rest, *a, **kw):
        out = np.array(real(self, rest, *a, **kw))
        out[0] = ~out[0]
        return out

    monkeypatch.setattr(engine.QueryEngine, "_device_batch", altered)


def _drop_half(monkeypatch):
    from repro.serve import planner

    real = planner.BatchPlan.scatter

    def half(self, tier_results):
        out = real(self, tier_results)
        out[self.n_queries // 2:] = False
        return out

    monkeypatch.setattr(planner.BatchPlan, "scatter", half)


@pytest.mark.parametrize("workload", SERVING)
@pytest.mark.parametrize("fault", [_flip_first, _drop_half])
def test_serving_fault_is_caught(root, workload, fault, monkeypatch, capsys):
    fault(monkeypatch)
    assert _correct(root, workload, capsys) is False

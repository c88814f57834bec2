"""The trace reduction, on a synthetic trace and on one recorded here."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench.tracing import WindowTrace
from bench.yardstick import trace_reduce as tr
from bench.yardstick.trace_reduce import Event, Trace

MS = 1_000_000


def synthetic() -> Trace:
    dev = "/device:TPU:0"
    modules = [Event("jit__tier_intersect(1)", 10 * MS, 20 * MS),
               Event("jit__tier_intersect(1)", 15 * MS, 30 * MS),   # overlaps the first
               Event("jit_wave_step(2)", 50 * MS, 60 * MS)]
    ops = [Event("%frontier_or_pallas.6 = u32[8,128] custom-call", 52 * MS, 55 * MS),
           Event("%fusion.1 = s32[4] fusion", 11 * MS, 12 * MS)]
    host = [Event("bench.window", 0, 100 * MS),
            Event("dispatch", 30 * MS, 48 * MS),
            Event("device_call", 35 * MS, 45 * MS),
            Event("build.wave", 60 * MS, 100 * MS)]
    return Trace(modules={dev: modules}, ops={dev: ops}, host=host)


def test_busy_is_the_union_of_program_runs():
    t = synthetic()
    assert tr.busy_seconds(t, 0, 100 * MS) == pytest.approx(0.030)
    assert tr.busy_seconds(t, 0, 25 * MS) == pytest.approx(0.015)


def test_time_by_name_and_totals():
    t = synthetic()
    assert tr.total_time(t.modules, 0, 100 * MS, "_tier_intersect") == (pytest.approx(0.025), 2)
    assert tr.total_time(t.ops, 0, 100 * MS, "frontier_or") == (pytest.approx(0.003), 1)
    assert tr.top_ops(t, 0, 100 * MS)[0][0].startswith("%frontier_or")


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = dict(tr.idle_gaps(synthetic(), 0, 100 * MS))
    # gaps: [0,10) window, [30,50) middle 40 in device_call, [60,100) build.wave
    assert gaps == {"bench.window": pytest.approx(0.010), "device_call": pytest.approx(0.020),
                    "build.wave": pytest.approx(0.040)}


def test_union():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_a_recorded_trace_reads_back():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    wt = WindowTrace(True)
    with wt.window():
        f(x).block_until_ready()
    wt.reduce()
    fields = wt.device_fields()
    assert 0 < fields["window_s"] < 60 and fields["busy_s"] >= 0
    assert any(e.name == "bench.window" for e in wt.trace.host)
    assert set(wt.breakdown()) == {"device_ops", "idle_gaps"}

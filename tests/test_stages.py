"""Stage spans and counters through the serving tick, on the profiler's clock.

  * the ring's timestamps agree with the profiler's host events (one clock),
  * a ``begin``/``end`` span with ``annotate=True`` keeps its annotation open
    across an ``await`` and enters the profile once,
  * in a daemon run every stage of the engine's path (the fused program,
    or the host prefilter and tier plan for a store too wide to fuse) is
    observed once per tick, and the worker's stages plus the hand-off
    reconcile with ``daemon_dispatch_ms``,
  * the collector hook records a forced collection and is gone after the
    daemon stops,
  * with obs off no stage is timed.
"""
import asyncio
import contextlib
import gc
import glob
import os

import numpy as np
import pytest

from repro import obs
from repro.core.api import build_oracle
from repro.graph.generators import random_dag
from repro.graph.reach import sample_reachability_batch
from repro.obs import metrics, trace
from repro.obs.stages import GC_PAUSES, NO_TICK
from repro.serve import daemon as daemon_mod
from repro.serve import engine as engine_mod
from repro.serve.daemon import DaemonConfig, ServeDaemon

G = random_dag(400, 1600, seed=11)
DAEMON_STAGES = ("wait", "collect", "handoff", "pad", "resolve")
ENGINE_STAGES = ("map", "prefilter", "plan", "enqueue", "sync", "scatter")
# the stages each device path observes: the fused program has no host
# prefilter or tier plan
PATH_STAGES = {"fused": ("map", "enqueue", "sync", "scatter"), "tiered": ENGINE_STAGES}


@pytest.fixture(scope="module")
def co():
    return build_oracle(G, backend="dense")


@pytest.fixture(autouse=True)
def _obs_enabled_after():
    yield
    obs.enable()
    trace.TRACER.jax_annotations = False


def _profile(body, tmp_path):
    """Run ``body`` under a CPU profiler trace with annotations mirrored;
    returns (profile_start_time ns, host events as (name, start_ns))."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    trace.TRACER.jax_annotations = True
    try:
        body()
    finally:
        trace.TRACER.jax_annotations = False
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    start = None
    host = []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns)) for e in line.events)
    return int(start), host


def _histograms(name):
    fam = metrics.snapshot()[name]["values"]
    return {label.split("=", 1)[1]: v for label, v in fam.items()}


# ---------------------------------------------------------------- the clock


def test_ring_timestamp_agrees_with_profiler_host_event(tmp_path):
    tr = trace.TRACER
    tr.clear()

    def body():
        with tr.span("clock_probe", cat="test", annotate=True):
            sum(range(1000))

    start, host = _profile(body, tmp_path)
    ring = [e for e in tr.events if e["name"] == "clock_probe"]
    events = [s for name, s in host if name == "clock_probe"]
    assert len(ring) == 1 and len(events) == 1
    gap_ns = abs(ring[0]["ts"] * 1000 - (start + events[0]))
    assert gap_ns < 1_000_000, gap_ns


def test_begun_span_across_await_enters_profile_once(tmp_path):
    tr = trace.TRACER
    tr.clear()

    async def crosses():
        tok = tr.begin("await_probe", cat="test", annotate=True)
        assert tok[-1] is not None            # the annotation is open
        await asyncio.sleep(0.01)
        await asyncio.sleep(0)
        tr.end(tok)

    _, host = _profile(lambda: asyncio.run(crosses()), tmp_path)
    assert [name for name, _ in host].count("await_probe") == 1
    ring = [e for e in tr.events if e["name"] == "await_probe"]
    assert len(ring) == 1 and ring[0]["dur"] >= 10_000 * 0.9


def test_begin_without_profiler_opens_no_annotation():
    tr = trace.Tracer(capacity=8)
    tok = tr.begin("plain", annotate=True)     # jax_annotations is off
    assert tok[-1] is None
    tr.end(tok)
    assert [e["name"] for e in tr.events] == ["plain"]


# ------------------------------------------------------------ a daemon run


def _serve(co, requests, cfg=None, during=None):
    """Submit every request (all at once), drain; returns the daemon."""

    async def go():
        daemon = ServeDaemon(co, cfg or DaemonConfig(
            batch_window_ms=1.0, max_batch=4096, deadline_ms=60_000.0,
            backend="dense"))
        await daemon.start()
        for reqs in requests:
            await asyncio.gather(*(daemon.submit(q) for q in reqs))
            if during is not None:
                during(daemon)
        await daemon.drain()
        return daemon

    return asyncio.run(go())


def _waves(n_waves=6, per_wave=4, pairs=1024, seed=3):
    """Requests that each need the label tiers: half reachable pairs."""
    rng = np.random.default_rng(seed)
    return [[sample_reachability_batch(G, pairs, rng)[0] for _ in range(per_wave)]
            for _ in range(n_waves)]


@contextlib.contextmanager
def _on_path(path):
    """The engine serves G's narrow store on ``path``: "tiered" lowers the
    fused program's width limit below the store."""
    with pytest.MonkeyPatch.context() as mp:
        if path == "tiered":
            mp.setattr(engine_mod, "FUSED_MAX_WIDTH", 0)
        yield


@pytest.fixture(scope="module", params=sorted(PATH_STAGES))
def staged_run(co, request):
    path = request.param
    with _on_path(path):
        co.engine.warmup(4096)                 # compiles every shape first
        metrics.REGISTRY.reset()
        # a collection landing between two stages would count in no stage;
        # the collector's pauses have their own test below
        gc.disable()
        try:
            daemon = _serve(co, _waves(40))
        finally:
            gc.enable()
        return path, daemon, metrics.snapshot()


def _stage_values(snap, family):
    return {label.split("=", 1)[1]: v for label, v in snap[family]["values"].items()}


def test_every_stage_observed_once_per_tick(staged_run):
    path, daemon, snap = staged_run
    ticks = daemon.counters["batches"]
    assert ticks > 0 and daemon.counters["device_batches"] == ticks
    d = _stage_values(snap, "daemon_stage_ms")
    e = _stage_values(snap, "engine_stage_ms")
    stages = PATH_STAGES[path]
    assert {s: d[s]["count"] for s in DAEMON_STAGES} == dict.fromkeys(DAEMON_STAGES, ticks)
    assert {s: e[s]["count"] for s in stages} == dict.fromkeys(stages, ticks)
    assert all(e[s]["count"] == 0 for s in e if s not in stages)
    assert all(v["sum"] >= 0 for v in list(d.values()) + list(e.values()))
    assert _stage_values(snap, "engine_device_batches_total") == {
        p: ticks if p == path else 0 for p in PATH_STAGES}


def test_worker_stages_reconcile_with_dispatch_ms(staged_run):
    path, _, snap = staged_run
    d = _stage_values(snap, "daemon_stage_ms")
    e = _stage_values(snap, "engine_stage_ms")
    dispatch = snap["daemon_dispatch_ms"]["values"][""]["sum"]
    parts = (d["handoff"]["sum"] + d["pad"]["sum"]
             + sum(e[s]["sum"] for s in PATH_STAGES[path]))
    assert parts <= dispatch * 1.0001
    assert parts >= 0.9 * dispatch, (parts, dispatch)


@pytest.mark.parametrize("path", sorted(PATH_STAGES))
def test_stage_spans_are_in_the_ring(co, path):
    trace.TRACER.clear()
    with _on_path(path):
        _serve(co, _waves(2))
    names = {e["name"] for e in trace.TRACER.events}
    assert {f"daemon.{s}" for s in ("wait", "collect", "pad", "resolve")} <= names
    assert {f"engine.{s}" for s in PATH_STAGES[path]} <= names
    assert not {f"engine.{s}" for s in ENGINE_STAGES if s not in PATH_STAGES[path]} & names
    assert {"dispatch_tick", "dispatch"} <= names
    assert "device_call" not in names


def test_stage_buckets_reach_from_tens_of_us_to_seconds():
    for fam in ("daemon_stage_ms", "engine_stage_ms", "process_gc_pause_ms"):
        bounds = metrics.REGISTRY.get(fam).buckets
        assert bounds[0] <= 0.01 and bounds[-1] >= 5000
        assert sum(b < 0.1 for b in bounds) >= 3    # tens of µs apart


def test_obs_off_times_no_stage(co):
    metrics.REGISTRY.reset()
    obs.disable()
    try:
        assert daemon_mod._STAGES.tick() is NO_TICK
        assert engine_mod._STAGES.tick() is NO_TICK
        _serve(co, _waves(2))
    finally:
        obs.enable()
    for fam in ("daemon_stage_ms", "engine_stage_ms"):
        assert all(v["count"] == 0 for v in _histograms(fam).values())


# --------------------------------------------------------------- collector


@pytest.mark.parametrize("stop", ["drain", "kill"])
def test_gc_hook_records_a_forced_collection_then_goes(co, stop):
    metrics.REGISTRY.reset()
    trace.TRACER.clear()
    assert GC_PAUSES not in gc.callbacks

    async def go():
        daemon = ServeDaemon(co, DaemonConfig(backend="dense"))
        await daemon.start()
        assert GC_PAUSES in gc.callbacks
        gc.collect()
        await daemon.submit(_waves(1, 1)[0][0])
        if stop == "drain":
            await daemon.drain()
        else:
            await daemon.kill()

    asyncio.run(go())
    assert GC_PAUSES not in gc.callbacks
    full = _histograms("process_gc_pause_ms")["2"]
    assert full["count"] >= 1 and full["sum"] > 0
    assert [e for e in trace.TRACER.events if e["name"] == "gc.collect"]
    gc.collect()                               # after the daemon: not recorded
    assert _histograms("process_gc_pause_ms")["2"]["count"] == full["count"]


def test_removed_duplicate_families_stay_gone():
    names = set(metrics.REGISTRY.names())
    assert "engine_verdict_uncertain_total" not in names
    assert "daemon_budget_steps_total" not in names
    assert {"daemon_stage_ms", "engine_stage_ms", "process_gc_pause_ms"} <= names

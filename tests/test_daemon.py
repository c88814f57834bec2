"""Serving daemon tests: admission control + shedding, deadline handling,
circuit breaker, pinned-epoch publishes, lifecycle (drain/kill), and the
stats/health surfaces.

Everything runs on small graphs with ``asyncio.run`` directly (no async
test plugin); where wall-clock matters the margins are coarse (a 150ms
injected stall against a 30ms deadline), so the assertions hold under CI
scheduling jitter.
"""
import asyncio
import time
import warnings

import numpy as np
import pytest

from repro.core.api import build_oracle
from repro.dynamic import DynamicOracle, UpdateBatch
from repro.ft import inject
from repro.graph.generators import random_dag
from repro.serve.daemon import (
    CircuitBreaker,
    DaemonConfig,
    ServeDaemon,
    ShedError,
)

G = random_dag(300, 1000, seed=7)


@pytest.fixture(scope="module")
def co():
    return build_oracle(G)


def _queries(rng, k=64):
    return rng.integers(0, G.n, size=(k, 2)).astype(np.int32)


# ------------------------------------------------------------ happy path


def test_roundtrip_answers_match_host_then_drains_clean(co, rng):
    qs = [_queries(rng) for _ in range(5)]
    want = [co.engine.query_batch(q, backend="host") for q in qs]

    async def go():
        daemon = ServeDaemon(co, DaemonConfig(batch_window_ms=1.0))
        await daemon.start()
        got = await asyncio.gather(*(daemon.submit(q) for q in qs))
        stats = await daemon.drain()
        return daemon, got, stats

    daemon, got, stats = asyncio.run(go())
    for w, g_ in zip(want, got):
        assert (w == g_).all()
    assert daemon.state == "stopped"
    assert stats["answered"] == stats["admitted"] == 5 * 64
    assert daemon.health()["ready"] is False
    assert daemon.health()["queue_depth"] == 0


# ------------------------------------------------------------- admission


def test_queue_full_sheds(co, rng):
    async def go():
        daemon = ServeDaemon(co, DaemonConfig(queue_limit=64))
        daemon.state = "ready"   # admission open, batch loop deliberately off
        first = asyncio.ensure_future(daemon.submit(_queries(rng, 64)))
        await asyncio.sleep(0)   # let it enqueue
        with pytest.raises(ShedError) as ei:
            await daemon.submit(_queries(rng, 1))
        first.cancel()
        return ei.value.reason, daemon.counters["shed_queue_full"]

    reason, n = asyncio.run(go())
    assert reason == "queue_full"
    assert n == 1


def test_deadline_budget_sheds_at_admission(co, rng):
    async def go():
        daemon = ServeDaemon(co, DaemonConfig())
        daemon.state = "ready"
        daemon._rate_qps = 50.0   # 64 queries => ~1.3s estimated wait
        with pytest.raises(ShedError) as ei:
            await daemon.submit(_queries(rng, 64), deadline_ms=10.0)
        return ei.value.reason

    assert asyncio.run(go()) == "deadline"


def test_draining_state_sheds(co, rng):
    async def go():
        daemon = ServeDaemon(co, DaemonConfig())
        daemon.state = "draining"
        with pytest.raises(ShedError) as ei:
            await daemon.submit(_queries(rng, 4))
        return ei.value.reason

    assert asyncio.run(go()) == "draining"


def test_expired_in_queue_sheds_at_dispatch(co, rng):
    """A request whose budget dies while an injected stall holds the
    dispatch must shed as ``expired``, never be served late."""
    plan = inject.Injector(latency={"serve.device_dispatch": ([0], 0.15)})

    async def go():
        daemon = ServeDaemon(
            co, DaemonConfig(batch_window_ms=1.0, backend="dense"))
        await daemon.start()
        with inject.active(plan):
            slow = asyncio.ensure_future(
                daemon.submit(_queries(rng), deadline_ms=5000.0))
            await asyncio.sleep(0.03)   # stalled dispatch now in flight
            doomed = asyncio.ensure_future(
                daemon.submit(_queries(rng, 32), deadline_ms=30.0))
            ans = await slow
            with pytest.raises(ShedError) as ei:
                await doomed
        await daemon.drain()
        return ans, ei.value.reason, daemon.counters["shed_expired"]

    ans, reason, n_expired = asyncio.run(go())
    assert ans.shape == (64,)
    assert reason == "expired"
    assert n_expired == 32


# --------------------------------------------------------------- breaker


def test_breaker_unit_lifecycle():
    br = CircuitBreaker(failures=2, backoff_s=1.0, backoff_max_s=4.0)
    assert br.allow_device(0.0)
    br.record(False, 0.0)
    assert br.state == "closed"          # one failure: under threshold
    br.record(False, 0.0)
    assert br.state == "open" and br.trips == 1
    assert not br.allow_device(0.5)      # backoff still running
    assert br.allow_device(1.5)          # elapsed: half_open probe allowed
    br.record(False, 1.5)                # failed probe: reopen, doubled
    assert br.state == "open" and br.backoff == 2.0 and br.trips == 2
    assert br.allow_device(4.0)
    br.record(True, 4.0)                 # healthy probe: closed, full reset
    assert br.state == "closed" and br.backoff == 1.0


def test_consecutive_device_failures_trip_breaker_then_reprobe(co, rng):
    plan = inject.Injector({"serve.device_dispatch": [0, 1]})
    q_check = _queries(rng, 32)
    want = co.engine.query_batch(q_check, backend="host")

    async def go():
        daemon = ServeDaemon(co, DaemonConfig(
            batch_window_ms=1.0, backend="dense", deadline_ms=10_000.0,
            breaker_failures=2, breaker_backoff_ms=60.0))
        await daemon.start()
        rng2 = np.random.default_rng(0)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with inject.active(plan):
                # two failing dispatches: engine downgrades each to host
                # (answers stay correct), breaker counts and trips
                for _ in range(2):
                    await daemon.submit(_queries(rng2))
                tripped = daemon.breaker.state
                # breaker open: batches route straight to host
                await daemon.submit(_queries(rng2))
                host_batches = daemon.counters["breaker_host_batches"]
                await asyncio.sleep(0.1)   # past the backoff: re-probe
                await daemon.submit(_queries(rng2))
                reprobed = daemon.breaker.state
        await daemon.drain()
        return daemon, tripped, host_batches, reprobed

    daemon, tripped, host_batches, reprobed = asyncio.run(go())
    assert tripped == "open"
    assert daemon.breaker.trips == 1
    assert host_batches >= 1
    assert reprobed == "closed"          # healthy probe closed it
    assert daemon.engine.degradation["device_to_host"] > 0
    # every answer correct throughout (spot check one fresh batch)
    got = asyncio.run(_one_shot(daemon.target, q_check))
    assert (got == want).all()


async def _one_shot(target, q):
    daemon = ServeDaemon(target, DaemonConfig(batch_window_ms=1.0))
    await daemon.start()
    ans = await daemon.submit(q)
    await daemon.drain()
    return ans


def test_latency_slo_breach_trips_breaker(co, rng):
    plan = inject.Injector(latency={"serve.device_dispatch": ([0], 0.08)})

    async def go():
        daemon = ServeDaemon(co, DaemonConfig(
            batch_window_ms=1.0, backend="dense",
            breaker_failures=1, breaker_slo_ms=20.0))
        await daemon.start()
        with inject.active(plan):
            ans = await daemon.submit(_queries(rng))
        state = daemon.breaker.state
        await daemon.drain()
        return ans, state, daemon.breaker.trips

    ans, state, trips = asyncio.run(go())
    assert ans.shape == (64,)
    assert state == "open" and trips == 1


# ------------------------------------------------- pinned-epoch publishes


def test_publish_pins_epoch_and_new_epoch_serves_after(rng):
    g = random_dag(200, 600, seed=3)
    dyn = DynamicOracle(g)
    q = rng.integers(0, g.n, size=(256, 2)).astype(np.int32)
    want_old = dyn.serve(q)
    topo_edges = [(int(u), int(v)) for u, v in
                  zip(rng.integers(0, g.n // 2, 8),
                      rng.integers(g.n // 2, g.n, 8)) if u != v]
    batch = UpdateBatch.of(inserts=topo_edges)
    plan = inject.Injector(latency={"dynamic.publish": ([0], 0.2)})

    async def go():
        daemon = ServeDaemon(dyn, DaemonConfig(batch_window_ms=1.0,
                                               deadline_ms=10_000.0))
        await daemon.start()
        with inject.active(plan):
            pub = asyncio.ensure_future(daemon.publish(batch))
            await asyncio.sleep(0.05)    # publish pinned + stalled
            assert daemon.health()["publishing"] is True
            during = await daemon.submit(q)
            epoch = await pub
        after = await daemon.submit(q)
        await daemon.drain()
        return daemon, during, after, epoch

    daemon, during, after, epoch = asyncio.run(go())
    # the batch dispatched mid-publish served from the pinned epoch: its
    # verdicts are exactly the pre-publish verdicts
    assert daemon.counters["pinned_epoch_batches"] >= 1
    assert (during == want_old).all()
    assert epoch >= 1
    assert daemon.counters["publishes"] == 1
    ref = DynamicOracle(g)
    ref.apply(batch)
    ref.publish()
    assert (after == ref.serve(q)).all()


# ------------------------------------------------------------- lifecycle


def test_kill_fails_pending_and_closes_admission(co, rng):
    async def go():
        daemon = ServeDaemon(co, DaemonConfig())
        daemon.state = "ready"   # loop off: requests stay queued
        pend = asyncio.ensure_future(daemon.submit(_queries(rng)))
        await asyncio.sleep(0)
        await daemon.kill()
        with pytest.raises(ShedError) as ei:
            await pend
        reason = ei.value.reason
        with pytest.raises(ShedError) as ei2:
            await daemon.submit(_queries(rng, 4))
        return daemon, reason, ei2.value.reason

    daemon, reason, after_reason = asyncio.run(go())
    assert reason == "killed"
    assert daemon.state == "killed"
    assert after_reason == "draining"
    assert daemon.counters["shed_killed"] == 64


# --------------------------------------------------- stats/health surfaces


def test_engine_stats_snapshot_is_consistent_copy(co, rng):
    co.engine.query_batch(_queries(rng), backend="host")
    s = co.engine.stats()
    assert s["backend"] in ("host", "dense", "kernel")
    assert s["last_batch"]["n_queries"] == 64
    # mutating the snapshot must not leak into the engine
    s["degradation"]["searched"] = 10 ** 9
    s["last_batch"]["n_queries"] = -1
    s2 = co.engine.stats()
    assert s2["degradation"]["searched"] != 10 ** 9
    assert s2["last_batch"]["n_queries"] == 64


def test_engine_reset_stats(co, rng):
    qmask = np.ones(co.oracle.n, dtype=bool)
    co.engine.set_quarantine(qmask, None)
    co.engine.query_batch(_queries(rng), backend="host")
    co.engine.set_quarantine(None, None)
    assert co.engine.degradation["searched"] > 0
    co.engine.reset_stats()
    assert all(v == 0 for v in co.engine.degradation.values())
    assert co.engine.stats()["last_batch"] == {}


def test_engine_deadline_degrades_to_host_same_verdicts(co, rng):
    q = _queries(rng, 128)
    want = co.engine.query_batch(q, backend="host")
    got = co.engine.query_batch(q, backend="dense",
                                deadline=time.monotonic() - 1.0)
    assert (got == want).all()
    assert co.engine.last_stats["degraded"]["deadline_to_host"] > 0


def test_health_surfaces_breaker_and_degradation(co, rng):
    async def go():
        daemon = ServeDaemon(co, DaemonConfig())
        await daemon.start()
        await daemon.submit(_queries(rng))
        h = daemon.health()
        await daemon.drain()
        return h

    h = asyncio.run(go())
    assert h["ready"] is True
    assert h["breaker"]["state"] == "closed"
    assert h["counters"]["answered"] == 64
    assert "degradation" in h["engine"]
    assert h["shed_rate"] == 0.0


# ------------------------------------------------------------- warm-up


@pytest.mark.parametrize("path", ["fused", "tiered"])
def test_engine_warmup_runs_every_tier_shape(co, path, monkeypatch):
    """The fused program at every tile, or, for a store too wide to fuse,
    every (tile, tier width) program."""
    from repro.serve import engine as engine_mod

    if path == "tiered":
        monkeypatch.setattr(engine_mod, "FUSED_MAX_WIDTH", 0)
    eng = co.engine
    before = dict(eng.degradation)
    rows = 0
    size = eng.min_tile
    while True:
        rows += 1
        if size >= 512:
            break
        size *= 2
    per_tile = 1 if path == "fused" else len(eng.widths)
    assert eng.warmup(512, backend="dense") == rows * per_tile
    assert eng.warmup(512, backend="host") == 0
    assert eng.degradation == before


@pytest.mark.parametrize("program", ["_tier_intersect_fused", "_tier_intersect"])
def test_open_loop_warmup_raises_device_program_faults(co, monkeypatch, program):
    """A device program that fails before the clock starts is a fault in
    the program: it raises, instead of being served on the host ladder.
    The fused program serves this narrow store; the tier programs serve it
    once the width limit is below it."""
    from repro.serve import engine as engine_mod
    from repro.serve.openloop import run_open_loop

    def broken(*a, **k):
        raise RuntimeError("tier program failed to lower")

    before = dict(co.engine.degradation)
    if program == "_tier_intersect":
        monkeypatch.setattr(engine_mod, "FUSED_MAX_WIDTH", 0)
    monkeypatch.setattr(engine_mod, program, broken)
    with pytest.raises(RuntimeError, match="failed to lower"):
        run_open_loop(co, G, duration_s=0.2,
                      config=DaemonConfig(backend="dense", max_batch=256))
    assert co.engine.degradation == before


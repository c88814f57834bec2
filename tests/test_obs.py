"""Observability layer tests: metrics registry semantics, tracer/Chrome
export structure, the disabled no-op path, and the two end-to-end contracts
the layer exists for —

  * a deterministic faulted daemon run exports a timeline that STRUCTURALLY
    contains the request lifecycle (admission span, expired-shed terminal
    event, breaker-open event, host-rung dispatch span), validated by
    event ph/cat/name/args rather than string matching, and
  * the registry snapshot reconciles exactly with the daemon's own shed /
    served counters (the registry is the substrate under ``health()``, not
    a second set of books),

plus the README drift guard: every registered metric family must be
documented in the README metric table.
"""
import asyncio
import json
import warnings

import numpy as np
import pytest

from repro import obs
from repro.core.api import build_oracle
from repro.ft import inject
from repro.graph.generators import random_dag
from repro.obs import metrics, trace
from repro.serve.daemon import (
    _COUNTER_METRICS,
    DaemonConfig,
    ServeDaemon,
    ShedError,
)

G = random_dag(300, 1000, seed=7)


@pytest.fixture(scope="module")
def co():
    return build_oracle(G)


@pytest.fixture(autouse=True)
def _obs_enabled_after():
    """No test may leave the process-global obs switch off."""
    yield
    obs.enable()


def _queries(rng, k=64):
    return rng.integers(0, G.n, size=(k, 2)).astype(np.int32)


# ------------------------------------------------------------------ registry


def test_counter_labels_and_snapshot():
    c = metrics.counter("t_obs_requests_total", "test counter",
                        labelnames=("event",))
    a = c.labels(event="a")
    b = c.labels(event="b")
    assert c.labels(event="a") is a          # children are cached
    a.inc()
    a.inc(3)
    b.inc()
    snap = metrics.snapshot()["t_obs_requests_total"]
    assert snap["type"] == "counter"
    assert snap["labels"] == ["event"]
    assert snap["values"]["event=a"] == 4
    assert snap["values"]["event=b"] == 1
    assert metrics.REGISTRY.counter_value("t_obs_requests_total", event="a") == 4
    assert metrics.REGISTRY.counter_total("t_obs_requests_total") == 5


def test_reregistration_shares_family_but_rejects_shape_change():
    c1 = metrics.counter("t_obs_shared_total", labelnames=("kind",))
    c2 = metrics.counter("t_obs_shared_total", labelnames=("kind",))
    assert c1 is c2
    with pytest.raises(ValueError):
        metrics.gauge("t_obs_shared_total", labelnames=("kind",))
    with pytest.raises(ValueError):
        metrics.counter("t_obs_shared_total", labelnames=("other",))
    with pytest.raises(ValueError):
        c1.labels(wrong="x")


def test_reset_zeroes_values_but_keeps_bound_children():
    c = metrics.counter("t_obs_reset_total", labelnames=("k",))
    bound = c.labels(k="x")
    bound.inc(7)
    metrics.REGISTRY.reset()
    assert bound.value == 0
    bound.inc()                              # the module-level ref still works
    assert metrics.REGISTRY.counter_value("t_obs_reset_total", k="x") == 1


def test_histogram_buckets_and_overflow():
    h = metrics.histogram("t_obs_lat_ms", buckets=(1.0, 10.0))
    child = h.labels()
    for v in (0.2, 0.7, 5.0, 99.0):
        child.observe(v)
    snap = metrics.snapshot()["t_obs_lat_ms"]["values"][""]
    assert snap["buckets_le"] == [1.0, 10.0, "+Inf"]
    assert snap["counts"] == [2, 1, 1]
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(104.9)


def test_disabled_is_a_noop_everywhere():
    c = metrics.counter("t_obs_off_total")
    g = metrics.gauge("t_obs_off_gauge")
    h = metrics.histogram("t_obs_off_ms", buckets=(1.0,))
    tr = trace.Tracer(capacity=16)
    obs.disable()
    try:
        c.inc()
        g.set(5)
        h.observe(0.5)
        assert tr.span("s") is trace.NOOP_SPAN
        with tr.span("s", cat="x", args={"a": 1}):
            pass
        tr.event("e")
        assert tr.begin("b") is None
        tr.end(None)
    finally:
        obs.enable()
    assert metrics.REGISTRY.counter_value("t_obs_off_total") == 0
    # the bound child exists (binding is registration, not observation)
    # but no value ever landed
    assert metrics.snapshot()["t_obs_off_gauge"]["values"][""] is None
    assert metrics.snapshot()["t_obs_off_ms"]["values"][""]["count"] == 0
    assert len(tr.events) == 0


# -------------------------------------------------------------------- tracer


def test_chrome_payload_structure_and_ring_bound(tmp_path):
    tr = trace.Tracer(capacity=4)
    with tr.span("outer", cat="test", args={"trace_id": 42}) as sp:
        sp.event("mid", detail=1)            # inherits cat + trace_id
        sp.set(extra="late")
    tok = tr.begin("cross_thread", cat="test")
    tr.end(tok, outcome="done")
    payload = tr.chrome_payload(meta={"k": "v"})
    assert payload["displayTimeUnit"] == "ms"
    # the metadata names the ring's clock beside the caller's own keys
    assert payload["metadata"] == {"k": "v", "clock": trace.CLOCK}
    evs = payload["traceEvents"]
    assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
    outer = next(e for e in evs if e["name"] == "outer")
    assert outer["ph"] == "X" and outer["dur"] >= 0
    assert outer["args"] == {"trace_id": 42, "extra": "late"}
    mid = next(e for e in evs if e["name"] == "mid")
    assert mid["ph"] == "i" and mid["s"] == "t"
    assert mid["args"]["trace_id"] == 42 and mid["cat"] == "test"
    cross = next(e for e in evs if e["name"] == "cross_thread")
    assert cross["args"] == {"outcome": "done"}
    # export round-trips as plain JSON
    p = tmp_path / "t.json"
    tr.export_chrome(str(p))
    assert json.loads(p.read_text())["traceEvents"]
    # bounded ring: capacity 4 keeps only the newest 4
    for i in range(6):
        tr.event(f"e{i}")
    assert len(tr.events) == 4
    tr.clear()
    assert len(tr.events) == 0


# ---------------------------------------------- faulted end-to-end contracts


@pytest.fixture(scope="module")
def faulted_run(co):
    """One deterministic faulted daemon run, traced from a clean registry:
    occurrence 0 of ``serve.device_dispatch`` stalls 150ms (expiring a
    30ms-budget request queued behind it), occurrences 1-2 fail (tripping
    the 2-failure breaker), and a final submit serves on the host rung."""
    plan = inject.Injector({"serve.device_dispatch": [1, 2]},
                           latency={"serve.device_dispatch": ([0], 0.15)})
    metrics.REGISTRY.reset()
    trace.TRACER.clear()

    async def go():
        daemon = ServeDaemon(co, DaemonConfig(
            batch_window_ms=1.0, backend="dense", deadline_ms=10_000.0,
            breaker_failures=2, breaker_backoff_ms=60_000.0))
        await daemon.start()
        rng = np.random.default_rng(0)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with inject.active(plan):
                slow = asyncio.ensure_future(
                    daemon.submit(_queries(rng), deadline_ms=5000.0))
                await asyncio.sleep(0.03)    # stalled dispatch in flight
                doomed = asyncio.ensure_future(
                    daemon.submit(_queries(rng, 32), deadline_ms=30.0))
                await slow
                with pytest.raises(ShedError) as ei:
                    await doomed
                assert ei.value.reason == "expired"
                for _ in range(2):           # failures 1, 2: breaker trips
                    await daemon.submit(_queries(rng))
                assert daemon.breaker.state == "open"
                await daemon.submit(_queries(rng))   # breaker-open host rung
        await daemon.drain()
        return daemon

    daemon = asyncio.run(go())
    return daemon, trace.TRACER.chrome_payload(meta={"test": "faulted_run"})


def test_faulted_timeline_contains_request_lifecycle(faulted_run, tmp_path):
    daemon, payload = faulted_run
    evs = payload["traceEvents"]

    def spans(name, **want_args):
        return [e for e in evs if e["ph"] == "X" and e["name"] == name
                and all(e.get("args", {}).get(k) == v
                        for k, v in want_args.items())]

    def instants(name, **want_args):
        return [e for e in evs if e["ph"] == "i" and e["name"] == name
                and all(e.get("args", {}).get(k) == v
                        for k, v in want_args.items())]

    admissions = spans("admission")
    assert admissions and all(e["cat"] == "request" for e in admissions)
    # each admission carries the id the rest of the lifecycle references
    tids = {e["args"]["trace_id"] for e in admissions}
    assert len(tids) == len(admissions)

    expired = instants("shed", reason="expired")
    assert len(expired) == 1
    assert expired[0]["cat"] == "request"
    assert expired[0]["args"]["trace_id"] in tids

    trips = instants("breaker_open")
    assert len(trips) == 1 and trips[0]["cat"] == "daemon"
    assert trips[0]["args"]["trips"] == 1

    host_dispatch = spans("dispatch", rung="host")
    assert host_dispatch and host_dispatch[0]["cat"] == "daemon"
    # the breaker was open when the host rung served
    assert host_dispatch[0]["args"]["breaker"] == "open"
    # every retroactive queue span references an admitted request
    queue_spans = spans("queue")
    assert queue_spans
    assert all(e["args"]["trace_id"] in tids for e in queue_spans)
    assert any(e["args"]["expired"] for e in queue_spans)

    # faults themselves are on the timeline at their occurrence
    assert spans("fault.stall") and len(instants("fault.fail")) == 2

    # and the whole thing exports as a loadable chrome trace
    out = tmp_path / "faulted.json"
    trace.TRACER.export_chrome(str(out), meta={"test": "faulted_run"})
    loaded = json.loads(out.read_text())
    assert loaded["displayTimeUnit"] == "ms"
    assert {e["name"] for e in loaded["traceEvents"]} >= {
        "admission", "shed", "breaker_open", "dispatch"}


def test_metrics_snapshot_reconciles_with_daemon_counters(faulted_run):
    daemon, _ = faulted_run
    # the registry was reset at run start, so every mirrored counter must
    # equal the daemon's own books EXACTLY — no sampling, no drift
    for key, bound in _COUNTER_METRICS.items():
        assert bound.value == daemon.counters[key], key
    snap = metrics.snapshot()
    shed_total = sum(snap["daemon_shed_total"]["values"].values())
    c = daemon.counters
    assert shed_total == (c["shed_queue_full"] + c["shed_deadline"]
                          + c["shed_draining"] + c["shed_expired"]
                          + c["shed_killed"])
    assert snap["daemon_requests_total"]["values"]["event=answered"] == \
        c["answered"]
    assert metrics.REGISTRY.counter_total("faults_injected_total") == 3
    # latency histogram observed exactly the answered requests
    lat = snap["daemon_request_latency_ms"]["values"][""]
    assert lat["count"] == len(daemon.latencies)


# -------------------------------------------------------------- drift guard


def test_every_registered_metric_is_documented_in_readme():
    """Importing the wired layers registers every production metric family;
    each name must appear (backticked) in the README metric table."""
    import repro.build.engine        # noqa: F401
    import repro.dynamic.versioned   # noqa: F401
    import repro.ft.inject           # noqa: F401
    import repro.serve.daemon        # noqa: F401
    import repro.serve.engine        # noqa: F401

    import pathlib
    readme = (pathlib.Path(__file__).resolve().parent.parent
              / "README.md").read_text()
    undocumented = [
        name for name in metrics.REGISTRY.names()
        if not name.startswith("t_obs_") and f"`{name}`" not in readme
    ]
    assert not undocumented, (
        f"metric families missing from the README table: {undocumented}")

"""Serving cells: labels built on the host, queries through ``ServeDaemon``.

Set-up: the configuration's graph from the seed, ``build_oracle`` with the
configured label engine, the daemon with the configured knobs, every tier
program compiled (``QueryEngine.warmup``), the mix's requests drawn from
the seed (``yardstick/mix.py``) and one warm pass through the daemon.
Window: the mix's open or closed loop drives ``ServeDaemon.submit`` for
``seconds``.  Check, after the window and with the program freed: every
answered pair against the benchmark's own BFS.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import re
import time

import numpy as np

from bench import harness
from bench.drivers import Outcome, memory_peak_bytes
from bench.yardstick import graphs, mix, reach

TAIL = re.compile(r"p(\d+(?:\.\d+)?)_ms")

def graph_edges(config: dict, seed: int):
    """The configuration's graph (its own fixed generator seed), under
    vertex ids drawn from the run's seed (``graphs.relabel``)."""
    g = config["graph"]
    n, src, dst = graphs.table1_edges(g["n"], g["m"], g["family"], g["seed"], g.get("scale", 1.0))
    return graphs.relabel(n, src, dst, seed)


def narrow_hops(co, bits: int) -> None:
    """The control: serve hop ids cut to their low ``bits`` bits, the
    narrow label layout that would tempt a later change (ids collide)."""
    from repro.graph.csr import INVALID

    o = co.oracle
    mask = (1 << bits) - 1

    def cut(L):
        return np.where(L == INVALID, L, L & mask).astype(np.int32)

    co.engine.refresh(dataclasses.replace(o, L_out=cut(o.L_out), L_in=cut(o.L_in)),
                      epoch=co.engine.epoch)


class _FullCollections:
    """A ``gc.callbacks`` entry: the pause of each full collection, in ms
    (the host loop stops for it)."""

    def __init__(self):
        self.ms: list = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.ms.append(round((time.perf_counter() - self._t0) * 1e3, 3))


class Served:
    """The configuration's labels behind a warm daemon, ready for windows."""

    def __init__(self, config: dict, seed: int, variant=None):
        from repro.core.api import build_oracle
        from repro.graph.csr import from_edges
        from repro.serve.daemon import DaemonConfig, ServeDaemon

        self.n, src, dst = graph_edges(config, seed)
        self.adj = reach.Adjacency(self.n, src, dst)
        self.co = build_oracle(from_edges(self.n, src, dst), impl=config["serve"]["labels"])
        if variant == "control":
            narrow_hops(self.co, int(config["control"]["hop_bits"]))
        knobs = {k: config["serve"][k]
                 for k in ("deadline_ms", "batch_window_ms", "max_batch", "queue_limit")}
        self.daemon_config = DaemonConfig(**knobs)
        self.co.engine.warmup(self.daemon_config.max_batch)
        self.daemon = None

    def window(self, traffic: dict, seconds: float, wt, seed: int):
        """One window of the mix on a fresh daemon; returns the log, the
        program's counters over the window, and the payload lookup."""
        from repro.obs import metrics
        from repro.serve.daemon import ServeDaemon

        drive, payload_of, warm = mix.requests(traffic, self.adj, seconds,
                                               np.random.default_rng([seed, 1]), seed)
        daemon = self.daemon = ServeDaemon(self.co, self.daemon_config)

        async def session():
            await daemon.start()
            await asyncio.gather(*(daemon.submit(p) for p in warm))
            daemon.engine.reset_stats()
            metrics.REGISTRY.reset()
            # set-up's objects (graph, pool, payloads) leave the collector's
            # generations, so a full collection in the window walks only
            # what the window allocates
            gc.collect()
            gc.freeze()
            gc_before = [g["collections"] for g in gc.get_stats()]
            full_gc = _FullCollections()
            gc.callbacks.append(full_gc)
            try:
                with wt.window():
                    log = await drive(daemon.submit)
            finally:
                gc.callbacks.remove(full_gc)
            counters = metrics.snapshot()
            self.gc_collections = [g["collections"] - b for g, b in zip(gc.get_stats(), gc_before)]
            self.full_gc_ms = full_gc.ms
            await daemon.drain()
            return log, counters

        log, counters = asyncio.run(session())
        return log, counters, payload_of

    def labels(self) -> dict:
        return {"out_len": np.asarray(self.co.oracle.out_len),
                "in_len": np.asarray(self.co.oracle.in_len),
                "level": reach.topo_levels(self.adj), "n_hops": int(self.co.oracle.n),
                "n": self.n}


def answered(log, payload_of):
    """(pairs, answers, latencies, failed pairs, errors by reason) of a window."""
    ok = [k for k, e in enumerate(log.error) if e is None]
    lat = np.asarray([log.finish[k] - log.start[k] for k in ok])
    pairs = (np.concatenate([payload_of(log.index[k]) for k in ok]) if ok
             else np.zeros((0, 2), np.int32))
    answers = (np.concatenate([np.asarray(log.answer[k], dtype=bool) for k in ok]) if ok
               else np.zeros(0, bool))
    errors: dict = {}
    n_failed = log.pending * len(payload_of(0))
    for k, e in enumerate(log.error):
        if e is not None:
            r = getattr(e, "reason", type(e).__name__)
            errors[r] = errors.get(r, 0) + 1
            n_failed += len(payload_of(log.index[k]))
    return pairs, answers, lat, n_failed, errors


def wrong_answers(adj, pairs: np.ndarray, answers: np.ndarray) -> tuple:
    """(wrong count, reachable share) of the answers against plain BFS."""
    if not pairs.size:
        return 0, None
    keys = reach.closure(adj, np.unique(pairs[:, 0]))
    truth = reach.reaches(keys, adj.n, pairs[:, 0], pairs[:, 1])
    return int((truth != answers).sum()), float(truth.mean())


def run(cell, seed: int, seconds: float, wt, variant=None) -> Outcome:
    served = Served(cell.config, seed, variant)
    log, counters, payload_of = served.window(cell.traffic, seconds, wt, seed)
    peak = memory_peak_bytes()
    health = served.daemon.health()
    comp = np.asarray(served.co.comp)
    gc_runs, full_gc_ms = served.gc_collections, served.full_gc_ms
    labels = served.labels()
    adj = served.adj
    del served
    gc.collect()
    wt.reduce()

    pairs, answers, lat, n_failed, errors = answered(log, payload_of)
    t_check = time.monotonic()
    wrong, reachable = wrong_answers(adj, pairs, answers)
    span = max(log.end - log.t0, 1e-9)
    values = {"qps": pairs.shape[0] / span}
    for m in cell.end_to_end:
        tail = TAIL.fullmatch(m["name"])
        if tail and lat.size:   # p<q>_ms: the q-th percentile of every answered request
            values[m["name"]] = float(np.quantile(lat, float(tail.group(1)) / 100)) * 1e3
    run_rec = harness.Run(
        counters=counters, trace=wt.trace,
        trace_lo=wt.lo, trace_hi=wt.hi,
        late_s=np.asarray(log.late_s) if log.late_s else None, latency_s=lat,
        traced_pairs=comp[pairs] if pairs.size else pairs, labels=labels)
    return Outcome(
        values=values, t_window=log.t0, t_end=log.end,
        attempted=int(pairs.shape[0] + n_failed), failed=int(n_failed),
        compared={"wrong_answers": (wrong, 0), "unanswered_requests": (log.pending, 0)},
        memory_peak_bytes=peak, run=run_rec,
        notes={"answered_pairs": int(pairs.shape[0]), "requests": len(log.index) + log.pending,
               "reachable_share": reachable, "errors_by_reason": errors,
               "batches": health["counters"]["batches"],
               "device_batches": health["counters"]["device_batches"],
               "degradation": health["engine"]["degradation"],
               "breaker_trips": health["breaker"]["trips"],
               "max_latency_ms": float(lat.max()) * 1e3 if lat.size else None,
               "gc_collections_by_generation": gc_runs, "full_gc_pause_ms": full_gc_ms,
               "check_seconds": time.monotonic() - t_check})

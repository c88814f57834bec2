#!/usr/bin/env python3
"""Bring-up smoke test of the build-and-serve path on a TPU.

One chip (the default):

  1. builds the citeseer analogue at a quarter of the size of Table 1 of
     the paper (n = 173,486 of 693,947; the device build takes about eleven
     minutes there on a v5e, and did not finish in eleven at half the size)
     through ``build_oracle``, and checks that the sparse device wave engine
     built it and that its labels are byte-identical to the host ``wave``
     engine's;
  2. serves it through ``ServeDaemon`` on the Pallas ``kernel`` backend: an
     open-loop Poisson run, then a batch of about half reachable pairs,
     checked against BFS truth;
  3. fails unless no query left the device path (every degradation counter
     and breaker trip is 0, every dispatch ran on the device), the kernel
     tiers answered queries, and the compiled tier program holds the Pallas
     kernel (``tpu_custom_call``).

``--chips 4`` runs only the multi-device phase: labels built on the host,
the same positive-heavy batch served by ``sharded`` and ``sharded_hop`` on a
2x2 ("data", "model") mesh, compared with the one-chip ``kernel`` backend
and with BFS, with each device's bytes in use.

  python chip_smoke.py                # one chip
  python chip_smoke.py --chips 4      # four chips, multi-device phase only
  python chip_smoke.py --scale 0.5    # another fraction of the Table 1 size

It exits nonzero, without the result line, unless JAX's first device is a
TPU.  The last line of its output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

DATASET = "citeseer"
# fraction of the Table 1 size: on one v5e the device build takes about
# eleven minutes at 0.25 and did not finish in eleven at 0.5
SCALE = 0.25
RATE = 400.0          # open-loop arrivals per second, 64 queries each
DURATION_S = 3.0      # open-loop run length
N_TRUTH = 500         # open-loop answers checked against BFS
N_POSITIVE = 2048     # positive-heavy batch, about half reachable


def log(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def make_graph(scale: float, seed: int):
    from repro.graph.generators import PAPER_DATASETS, paper_dataset_analogue

    g = paper_dataset_analogue(DATASET, scale, seed=seed)
    log("graph", f"{DATASET}@{scale} n={g.n} m={g.m}")
    if scale < 1.0:
        log("scale_cut", f"{scale} of the Table 1 size (n={PAPER_DATASETS[DATASET]['n']})")
    return g


def positive_heavy(g, n_queries: int, seed: int):
    from repro.graph.reach import sample_reachability_batch

    q, truth = sample_reachability_batch(g, n_queries, np.random.default_rng(seed))
    log("positive_heavy_batch", f"{q.shape[0]} queries, {int(truth.sum())} reachable")
    return q, truth


def zero_degradation(deg: dict) -> bool:
    return not any(deg.values())


def serve_once(co, q: np.ndarray, backend: str) -> tuple:
    """One request through a fresh ServeDaemon; returns (answers, daemon)."""
    from repro.serve.daemon import DaemonConfig, ServeDaemon

    daemon = ServeDaemon(co, DaemonConfig(backend=backend, deadline_ms=60_000.0,
                                          max_batch=max(4096, q.shape[0])))

    async def go():
        await daemon.start()
        ans = await daemon.submit(q)
        await daemon.drain()
        return ans

    return asyncio.run(go()), daemon


def bytes_in_use() -> list:
    import jax

    return [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]


def labels_identical(a, b) -> bool:
    return all(np.asarray(getattr(a, f)).tobytes() == np.asarray(getattr(b, f)).tobytes()
               for f in ("L_out", "L_in", "out_len", "in_len", "hop_rank"))


def one_chip(args) -> None:
    import jax

    from repro.build.engine import build_distribution_labels
    from repro.core.api import build_oracle
    from repro.graph.scc import condense_to_dag
    from repro.serve.daemon import DaemonConfig
    from repro.serve.engine import _tier_intersect_fused
    from repro.serve.openloop import run_open_loop

    g = make_graph(args.scale, args.seed)

    t0 = time.perf_counter()
    co = build_oracle(g, backend="kernel")
    build_s = time.perf_counter() - t0
    stats = co.oracle.build_stats
    log("device_build_seconds", f"{build_s:.3f}")
    log("device_build_stats", json.dumps({k: stats[k] for k in (
        "impl", "schedule_seconds", "sweep_seconds", "n_waves")}))
    require(stats["impl"] == "device", f"build ran impl={stats['impl']!r}, not 'device'")

    dag, _ = condense_to_dag(g)
    t0 = time.perf_counter()
    host = build_distribution_labels(dag, impl="wave")
    log("host_wave_build_seconds", f"{time.perf_counter() - t0:.3f}")
    same = labels_identical(co.oracle, host)
    log("labels_byte_identical_to_wave", same)
    require(same, "device labels differ from the host wave engine's")
    del host
    label_bytes = co.oracle.L_out.nbytes + co.oracle.L_in.nbytes
    log("label_ints", co.oracle.total_label_size)
    log("label_matrix_bytes", label_bytes)
    log("tier_widths", co.engine.widths)

    # the program the engine dispatches (fused: the store is narrow) must
    # hold the Pallas kernel
    lo, li = co.oracle.device_labels()
    hlo = _tier_intersect_fused.lower(
        lo, li, co.engine._vertex_meta(co.oracle), jax.numpy.zeros((256, 2), jax.numpy.int32),
        use_kernel=True).compile().as_text()
    require("tpu_custom_call" in hlo, "compiled tier program holds no tpu_custom_call")
    log("tier_program_has_tpu_custom_call", True)

    rep = run_open_loop(co, g, rate_arrivals_per_s=RATE, duration_s=DURATION_S,
                        deadline_ms=150.0, seed=args.seed, n_truth=N_TRUTH,
                        config=DaemonConfig(backend="kernel", deadline_ms=150.0))
    log("open_loop", json.dumps({k: rep[k] for k in (
        "offered_qps", "sustained_qps", "p50_ms", "p99_ms", "shed_rate", "answered",
        "batches", "device_batches", "breaker", "degradation", "sample_errors")}))
    require(rep["sample_errors"] == 0, f"{rep['sample_errors']} open-loop answers disagree with BFS")
    require(zero_degradation(rep["degradation"]), f"open loop degraded: {rep['degradation']}")
    require(rep["breaker"]["trips"] == 0, f"breaker tripped {rep['breaker']['trips']} times")
    require(rep["device_batches"] == rep["batches"],
            f"{rep['batches'] - rep['device_batches']} open-loop batches left the device")

    q, truth = positive_heavy(g, N_POSITIVE, args.seed)
    ans, daemon = serve_once(co, q, "kernel")
    wrong = int((ans != truth).sum())
    tiers = co.engine.last_stats["tiers"]
    kernel_answered = sum(t["count"] for t in tiers)
    log("positive_heavy_wrong", wrong)
    log("positive_heavy_tiers", json.dumps(tiers))
    require(wrong == 0, f"{wrong} of {q.shape[0]} answers disagree with BFS")
    require(zero_degradation(co.engine.degradation),
            f"engine degraded: {co.engine.degradation}")
    require(daemon.breaker.trips == 0, "breaker tripped on the positive-heavy batch")
    require(daemon.counters["device_batches"] == daemon.counters["batches"],
            "the positive-heavy batch left the device")
    require(kernel_answered > 0, "the kernel tiers answered no queries")
    log("kernel_tier_answers", kernel_answered)
    log("checked_against_bfs", int(q.shape[0]) + min(N_TRUTH, rep["answered"]))
    log("device_bytes_in_use", bytes_in_use()[0])


def four_chips(args) -> None:
    import jax

    from repro.core.api import build_oracle
    from repro.serve.engine import QueryEngine

    require(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    g = make_graph(args.scale, args.seed)
    t0 = time.perf_counter()
    co = build_oracle(g, backend="host", impl="wave")
    log("host_wave_build_seconds", f"{time.perf_counter() - t0:.3f}")
    log("label_matrix_bytes", co.oracle.L_out.nbytes + co.oracle.L_in.nbytes)
    q, truth = positive_heavy(g, N_POSITIVE, args.seed)

    log("bytes_in_use_before", bytes_in_use())
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    answers = {}
    for backend in ("sharded_hop", "sharded", "kernel"):
        eng = QueryEngine(co.oracle, backend=backend, level=co.engine.level,
                          mesh=None if backend == "kernel" else mesh,
                          comp_source=co.engine.comp_source)
        t0 = time.perf_counter()
        answers[backend] = eng.query_batch(q)
        log(f"{backend}_seconds", f"{time.perf_counter() - t0:.3f}")
        log(f"{backend}_bytes_in_use", bytes_in_use())
        require(zero_degradation(eng.degradation), f"{backend} degraded: {eng.degradation}")
        wrong = int((answers[backend] != truth).sum())
        log(f"{backend}_wrong_vs_bfs", wrong)
        require(wrong == 0, f"{backend}: {wrong} answers disagree with BFS")
    for backend in ("sharded", "sharded_hop"):
        require(bool((answers[backend] == answers["kernel"]).all()),
                f"{backend} disagrees with the one-chip kernel backend")
    log("agree_with_kernel", True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=float, default=SCALE,
                    help="fraction of the Table 1 citeseer size")
    ap.add_argument("--seed", type=int, default=0, help="graph and query seed")
    args = ap.parse_args()

    log("compile_cache", enable_compile_cache())
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform!r})", file=sys.stderr)
        return 1
    log("device", f"{dev.device_kind} x{len(devices)}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args)
    log("total_seconds", f"{time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark driver — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only query_time
  PYTHONPATH=src python -m benchmarks.run --quick    # smoke mode

Prints ``name,us_per_call,derived`` CSV sections.  The construction section
also writes machine-readable ``BENCH_build.json`` (see
benchmarks/construction_time.py); ``--quick`` runs a one-dataset smoke of
the construction section (JSON goes to BENCH_build_quick.json so the
tracked full-grid record is never clobbered) so CI can exercise the
harness in seconds, while the full sweep remains this one command.
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default=None,
        choices=[None, "query_time", "construction_time", "index_size",
                 "kernel_bench", "serve_smoke", "obs_overhead"],
    )
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: construction section only, tiny dataset")
    ap.add_argument("--ci", action="store_true",
                    help="medium-cost CI tier: construction section on one "
                         "mid-size dataset at best-of-4 (so --check-monotone "
                         "gates the engine speedup RATIO; single-rep quick "
                         "rows are too noisy for that) plus a few-second "
                         "open-loop serving-daemon smoke with an injected "
                         "device fault (gated via the serve invariants)")
    ap.add_argument("--json-out", default=None,
                    help="where the construction section writes its JSON record "
                         "(default: BENCH_build.json, BENCH_build_quick.json "
                         "in --quick mode, BENCH_build_ci.json in --ci mode)")
    ap.add_argument("--check-monotone", action="store_true",
                    help="after the run, diff the fresh construction record "
                         "against the committed BENCH trajectory and exit "
                         "nonzero on a >10%% regression (index size growth, "
                         "engine-speedup drop, lost byte-identity, or recorded "
                         "serve sample errors)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.json_out is None:
        args.json_out = ("BENCH_build_ci.json" if args.ci
                         else "BENCH_build_quick.json" if args.quick
                         else "BENCH_build.json")

    from benchmarks import (
        construction_time,
        index_size,
        kernel_bench,
        obs_overhead,
        query_time,
        serve_sweep,
    )
    from benchmarks.common import check_monotone, load_trajectory

    # snapshot the committed trajectory before any section overwrites it
    trajectory = load_trajectory() if args.check_monotone else None

    serve_ci_json = "BENCH_serve_ci.json"
    sections = {
        "kernel_bench": kernel_bench.run,
        "index_size": index_size.run,
        "construction_time": lambda *, out: construction_time.run(
            out=out, quick=args.quick, ci=args.ci, json_out=args.json_out
        ),
        "query_time": query_time.run,
        "serve_smoke": lambda *, out: serve_sweep.ci_smoke(
            json_out=serve_ci_json, out=out),
        "obs_overhead": lambda *, out: obs_overhead.run(
            out=out, quick=args.quick, ci=args.ci),
    }
    if (args.quick or args.ci) and not args.only:
        # the CI tier adds the open-loop daemon smoke (faulted + clean) so
        # overload robustness is gated per push, not just when the full
        # serve benchmark is regenerated
        sections = {"construction_time": sections["construction_time"]}
        if args.ci:
            sections["serve_smoke"] = lambda *, out: serve_sweep.ci_smoke(
                json_out=serve_ci_json, out=out)
    flushing = lambda s: print(s, flush=True)
    t0 = time.perf_counter()
    ran = set()
    gate_failures = []
    for name, fn in sections.items():
        if args.only and name != args.only:
            continue
        print(f"\n## section: {name}", flush=True)
        result = fn(out=flushing)
        if isinstance(result, dict) and result.get("gate_failed"):
            gate_failures.append(name)
        ran.add(name)
    print(f"\n## total_bench_seconds,{time.perf_counter() - t0:.1f},", flush=True)
    if gate_failures:
        raise SystemExit(f"section gate failed: {', '.join(gate_failures)}")

    if args.check_monotone:
        if "construction_time" not in ran:
            # without a fresh record the diff would compare the committed
            # baseline against itself and pass vacuously
            raise SystemExit(
                "--check-monotone: the construction section did not run "
                f"(sections ran: {sorted(ran)}); drop --only")
        regressions = check_monotone(
            args.json_out, trajectory,
            serve_fresh_path=(serve_ci_json if "serve_smoke" in ran else None),
            out=flushing)
        if regressions:
            raise SystemExit(1)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Run one traced window of a cell, keep its trace, and list what is in it.

  python3 bench/tools/inspect_trace.py --workload <name> --seed <n> --seconds <s> --out <dir>

Prints each plane and line of the ``.xplane.pb`` with its event count and
the names that take the most time on it, so that a per-layer reader can be
pointed at the names the program's modules and kernels carry.
"""
from __future__ import annotations

import argparse
import collections
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from bench import drivers, harness
    from bench.tracing import WindowTrace
    from bench.yardstick import trace_reduce

    harness.use_compile_cache(ROOT)
    import jax
    from jax.profiler import ProfileData

    cell = harness.load_cell(ROOT, args.workload)
    wt = WindowTrace(True, keep_dir=str(pathlib.Path(args.out).resolve()))
    drivers.driver(cell.traffic["driver"]).run(cell, args.seed, args.seconds, wt)
    path = trace_reduce.find_xplane(wt.keep_dir)
    print("trace", path, pathlib.Path(path).stat().st_size, "bytes; devices", jax.devices())
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            acc = collections.defaultdict(lambda: [0, 0.0])
            for e in line.events:
                a = acc[e.name]
                a[0] += 1
                a[1] += e.duration_ns / 1e6
            top = sorted(acc.items(), key=lambda kv: -kv[1][1])[:12]
            print(f"{plane.name} | {line.name} | {sum(v[0] for v in acc.values())} events")
            for name, (cnt, ms) in top:
                print(f"    {ms:12.3f} ms {cnt:8d}x  {name[:160]}")
    print("window", wt.lo, wt.hi, wt.device_fields())
    print("breakdown", wt.breakdown())
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run a cell's control on several seeds and print what the check compares.

  python3 bench/control.py --workload <name> --seeds s1,s2,... --seconds <s>

The control is the cell's own path with one guarantee of its configuration
broken, as the configuration's ``control`` entry says: for a serving
configuration, hop ids cut to ``hop_bits`` bits (a narrow label layout
under which ids collide).  Each seed has to come out not correct.  The benchmark's own runs (``run.py``) never run it.  One line of
JSON per seed: the numbers compared, their limits, and ``correct``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--variant", default="control", help="control, or none for the program")
    args = ap.parse_args()
    from bench import drivers, harness
    from bench.tracing import WindowTrace

    harness.use_compile_cache(ROOT)
    cell = harness.load_cell(ROOT, args.workload)
    variant = None if args.variant == "none" else args.variant
    for seed in (int(s) for s in args.seeds.split(",")):
        out = drivers.driver(cell.traffic["driver"]).run(cell, seed, args.seconds,
                                                       WindowTrace(False), variant)
        print(json.dumps({"workload": args.workload, "variant": args.variant, "seed": seed,
                          "correct": all(v <= lim for v, lim in out.compared.values()),
                          "compared": {k: {"value": v, "limit": lim}
                                       for k, (v, lim) in out.compared.items()},
                          "values": out.values, "notes": {k: str(v) for k, v in out.notes.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

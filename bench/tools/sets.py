#!/usr/bin/env python3
"""Measure a cell's spread: the same seeds run as two sets, each run its own
process, one after another, and each metric's quartile spread per set.

  python3 bench/tools/sets.py --workload <name> --seeds s1,...,s6 --sets 2 \
      --seconds <s> [--trace 0] [--out results.jsonl]

A spread is (Q3 - Q1) / median, with Python's ``statistics.quantiles(n=4)``.
Each run's result line is appended to ``--out``; the summary is printed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    lines = {}
    for k in range(args.sets):
        for seed in seeds:
            t0 = time.monotonic()
            res = subprocess.run(
                [sys.executable, "bench/run.py", "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace", str(args.trace)],
                cwd=ROOT, capture_output=True, text=True)
            out = res.stdout.strip().splitlines()
            line = json.loads(out[-1]) if res.returncode == 0 and out else None
            rec = {"set": k, "seed": seed, "rc": res.returncode,
                   "wall_s": time.monotonic() - t0, "line": line,
                   "stderr_tail": "\n".join(res.stderr.strip().splitlines()[-25:])}
            lines[(k, seed)] = line
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
            m = {} if line is None else {n: v["value"] for n, v in line["metrics"].items()}
            print(json.dumps({"set": k, "seed": seed, "rc": res.returncode,
                              "correct": None if line is None else line["correct"],
                              "wall_s": round(rec["wall_s"], 1), "metrics": m,
                              "compared": None if line is None else line["compared"]}), flush=True)
            if line is None:
                print(rec["stderr_tail"], flush=True)
    names = sorted({n for ln in lines.values() if ln for n in ln["metrics"]})
    for name in names:
        per_set = []
        for k in range(args.sets):
            vals = [lines[(k, s)]["metrics"][name]["value"] for s in seeds
                    if lines.get((k, s)) and name in lines[(k, s)]["metrics"]]
            if len(vals) >= 2:
                per_set.append({"median": statistics.median(vals), "spread": spread(vals),
                                "values": vals})
        print(json.dumps({"metric": name, "sets": per_set}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

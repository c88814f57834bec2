"""A copy of the benchmark's spec and data files at a size the CPU runs in
seconds: the same cells, mixes and drivers, on small graphs.

It also adds the mixes of ``EXAMPLES`` as cells, each a data file and
spec entries only: a bursty open loop of the random set and a Zipf-hot
closed loop whose hot set moves.  They show that a new mix needs no code."""
from __future__ import annotations

import json
import pathlib
import shutil

import jax

REPO = pathlib.Path(__file__).resolve().parents[2]

SIZES = {  # config -> graph overrides
    "citeseer": {"n": 3000, "m": 9000},
}

EXAMPLES = {  # mix -> its file; each runs on citeseer
    "random.burst": {"driver": "serve", "request_pairs": 64, "arrivals_per_s": 200,
                     "burst_on_s": 0.2, "burst_off_s": 0.3},
    "zipf.closed": {"driver": "serve", "request_pairs": 256, "pool_pairs": 8192,
                    "reachable_share": 0.5, "zipf_s": 1.1, "hot_shift_requests": 8,
                    "clients": 2},
}


def make_root(dest: pathlib.Path, hop_bits: int = 4) -> pathlib.Path:
    """``dest`` with a tiny copy of BENCHMARK.json's files; returns it."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copytree(REPO / "bench" / "configs", dest / "bench" / "configs")
    shutil.copytree(REPO / "bench" / "traffic", dest / "bench" / "traffic")
    for path in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["graph"].update(SIZES.get(cfg["name"], {}))
        if "hop_bits" in cfg.get("control", {}):
            cfg["control"]["hop_bits"] = hop_bits   # ids of a tiny graph fit 16 bits
        path.write_text(json.dumps(cfg))
    for path in (dest / "bench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        if "arrivals_per_s" in t:
            t["arrivals_per_s"] = 100
        if "pool_pairs" in t:
            t["pool_pairs"] = 8192
        path.write_text(json.dumps(t))
    for mix, t in EXAMPLES.items():
        (dest / "bench" / "traffic" / f"{mix}.json").write_text(json.dumps(t))
        name = f"citeseer.{mix}"
        spec["workloads"].append({"name": name, "config": "citeseer", "traffic": mix,
                                  "chips": 1, "why": "example mix"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest


def cells() -> list:
    """Every cell of BENCHMARK.json, and the example mixes' cells."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    return [w["name"] for w in spec["workloads"]] + [f"citeseer.{m}" for m in EXAMPLES]


def cpu_devices(_chips: int):
    return jax.devices()

"""What every cell shares: the spec, the files a cell is made of, the run's
record that per-layer readers read, and the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  The configuration is the JSON file the
spec names; the traffic mix is ``bench/traffic/<traffic>.json``, data that
names the driver in ``bench/drivers/`` that runs it (``yardstick/mix.py``
says what a mix holds); a per-layer
metric ``<name>`` is read by ``bench/layer_metrics/<name>.py``, a module
with ``read(run) -> float | None``.  Nothing here names a cell.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent


def process_start_monotonic() -> float:
    """``time.monotonic()`` at the moment this process started (Linux
    ``/proc``); the time of this call where ``/proc`` cannot tell."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_s = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - start_s
        return time.monotonic() - max(age, 0.0)
    except (OSError, ValueError, IndexError, AttributeError):
        return time.monotonic()


def use_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache in ``.jax_cache/`` of the checkout,
    at a fixed path (the path is part of the cache key), keeping every
    program however fast it compiled.  The variable is set first, so the
    program's own ``repro.launch.compile_cache`` takes the same directory."""
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]   # the spec's entries this cell reports
    per_layer: List[dict]


def load_spec(root: pathlib.Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if applies(m) and m["moves"] in reported]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer)


@dataclasses.dataclass
class Run:
    """What a window left for the per-layer readers.  Every field a reader
    needs and a kind of cell does not have is None."""

    counters: Dict[str, Any]                 # program registry, window only
    trace: Any = None                        # trace_reduce.Trace of the window
    trace_lo: int = 0                        # traced window, ns of the trace clock
    trace_hi: int = 0
    late_s: Optional[np.ndarray] = None      # open loop: submit - due, per request
    latency_s: Optional[np.ndarray] = None   # serving: each answered request's latency
    traced_pairs: Optional[np.ndarray] = None  # answered inside the traced window
    labels: Optional[dict] = None            # out_len, in_len, level, n_hops, n
    peaks: Optional[dict] = None             # yardstick/peaks.json entry of the device


def counter_total(run: Run, name: str) -> Optional[float]:
    fam = run.counters.get(name)
    if fam is None:
        return None
    return float(sum(fam["values"].values()))


def histogram_mean(run: Run, name: str) -> Optional[float]:
    fam = run.counters.get(name)
    if fam is None:
        return None
    total = sum(v["sum"] for v in fam["values"].values())
    count = sum(v["count"] for v in fam["values"].values())
    return total / count if count else None


def device_idle_percent(run: Run) -> Optional[float]:
    """Share of the traced window in which no program ran on the device, in %."""
    from bench.yardstick import trace_reduce

    if run.trace is None or not run.trace.devices or run.trace_hi <= run.trace_lo:
        return None
    busy = trace_reduce.busy_seconds(run.trace, run.trace_lo, run.trace_hi)
    return 100.0 * (1.0 - busy / ((run.trace_hi - run.trace_lo) / 1e9))


def read_layer_metrics(cell: Cell, run: Run) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        path = BENCH_DIR / "layer_metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(f"bench_layer_{m['name']}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def print_result(correct: bool, attempted: int, failed: int, metrics: dict, device: dict,
                 compared: Dict[str, tuple], breakdown: Optional[dict] = None) -> None:
    """The contract line: stderr ends with each compared number beside its
    limit, stdout ends with one JSON object whose last key repeats them."""
    for name, (value, limit) in compared.items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    print(json.dumps(line), flush=True)

#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration and traffic mix are files under ``bench/`` (see
``bench/harness.py``).  Set-up loads and warms the cell, the window measures
for ``--seconds``, and the check compares what the window produced with the
benchmark's own reference.  With ``--trace 0`` the line carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window and from the program's counters.

It exits nonzero without a result line unless JAX's devices are TPUs, as
many as the cell asks for.  JAX's compilation cache is ``.jax_cache/`` in
the checkout and keeps every program, so only a checkout's first run
compiles.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

T_PROCESS = harness.process_start_monotonic()


def require_chips(chips: int):
    """The devices, if they are at least ``chips`` TPUs; else exit 3."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX has {len(devices)} "
              f"{devices[0].platform!r} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devices


def main(argv=None, root: pathlib.Path = ROOT, chip_check=require_chips, variant=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a whole number >= 0")

    cell = harness.load_cell(root, args.workload)
    harness.use_compile_cache(root)
    import jax

    devices = chip_check(cell.chips)

    from bench import drivers
    from bench.tracing import WindowTrace
    from bench.yardstick import peaks

    # backend compiles and persistent-cache loads, to count the window's
    events: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: events.append((time.monotonic(), event)))
    jax.monitoring.register_event_listener(
        lambda event, **_kw: events.append((time.monotonic(), event)))
    wt = WindowTrace(bool(args.trace))
    out = drivers.driver(cell.traffic["driver"]).run(cell, args.seed, args.seconds, wt, variant)
    inside = [e for t, e in events if out.t_window <= t <= out.t_end]
    loads = inside.count("/jax/compilation_cache/cache_hits")
    out.notes["window_compiles"] = inside.count("/jax/core/compile/backend_compile_duration") - loads
    out.notes["window_cache_loads"] = loads
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    for k, v in out.notes.items():
        print(f"note {k} {v}", file=sys.stderr)
    correct = all(value <= limit for value, limit in out.compared.values())
    breakdown = None
    if args.trace:
        if dev.platform == "tpu":
            out.run.peaks = peaks.peaks(dev.device_kind)
        device.update(wt.device_fields())
        metrics = harness.read_layer_metrics(cell, out.run)
        breakdown = wt.breakdown()
    else:
        values = dict(out.values, setup_s=out.t_window - T_PROCESS)
        metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end}
    harness.print_result(correct, out.attempted, out.failed, metrics, device,
                         out.compared, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())

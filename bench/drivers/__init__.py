"""Drivers: a traffic mix names its own in its ``driver`` key, and the
module of that name here runs it.

Each module has ``run(cell, seed, seconds, window_trace, variant=None)``
returning an ``Outcome``.  ``variant="control"`` runs the cell's control
(see ``bench/control.py``); the benchmark's own runs never pass it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

@dataclasses.dataclass
class Outcome:
    values: Dict[str, float]            # end-to-end metrics, setup_s aside
    t_window: float                     # time.monotonic() at the window's start
    t_end: float                        # ... and at its end
    attempted: int
    failed: int
    compared: Dict[str, Tuple[float, float]]   # name -> (value, limit)
    memory_peak_bytes: Optional[int]
    run: object                         # harness.Run for the per-layer readers
    notes: Dict[str, object] = dataclasses.field(default_factory=dict)


def driver(name: str):
    """The module ``bench/drivers/<name>.py``."""
    import importlib
    import pathlib

    if not (pathlib.Path(__file__).parent / f"{name}.py").is_file():
        raise SystemExit(f"no driver bench/drivers/{name}.py")
    return importlib.import_module(f"bench.drivers.{name}")


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None

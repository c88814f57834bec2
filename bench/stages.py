"""The program's stage histograms (``daemon_stage_ms{stage}``,
``engine_stage_ms{stage}``), read from a window's counters for the
per-layer readers.  A program without the family reads as None."""
from __future__ import annotations

from typing import Optional, Sequence


def mean_ms(run, family: str, stages: Sequence[str], per: str) -> Optional[float]:
    """The named stages' summed time over the window, in ms, per tick of the
    stage ``per`` (a stage that runs once in every tick or batch)."""
    fam = run.counters.get(family)
    if fam is None:
        return None
    by_stage = {label.split("=", 1)[1]: v for label, v in fam["values"].items()}
    ticks = by_stage.get(per, {}).get("count", 0)
    if not ticks:
        return None
    return sum(by_stage[s]["sum"] for s in stages if s in by_stage) / ticks

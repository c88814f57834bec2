"""Where a serving tick's time goes: its stages, and the collector's pauses.

A stage family is one labelled histogram (``<layer>_stage_ms{stage}``) plus
an ``annotate=True`` span per stage run, named ``<cat>.<stage>``:

    ENGINE = StageFamily("engine_stage_ms", "...", ("map", "plan"), cat="engine")
    st = ENGINE.tick()        # the shared no-op when obs is off
    with st("map"):
        ...
    st.observe()              # once per tick (or batch)

Runs of one stage within a tick add up (the engine's tiers each enqueue),
and ``observe`` records each stage that ran once, so a stage's histogram
counts the ticks it ran in and sums its time.  Stages run one after
another, never nested.  A stage that crosses an ``await`` is timed with
``begin``/``end``: its span keeps its ``TraceAnnotation`` open until then.

``GC_PAUSES`` is a ``gc.callbacks`` hook: while installed it records every
collection's pause in ``process_gc_pause_ms{generation}``, and spans each
full (generation 2) collection as ``gc.collect``, so an idle gap or a tail
request that a collection caused is named as one.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, Sequence

from repro.obs import metrics
from repro.obs.state import ON
from repro.obs.trace import NOOP_SPAN, TRACER

# tens of µs (one numpy pass over a tick's pairs) up to the multi-second
# stalls a slow stage or a full collection can cause
STAGE_BUCKETS_MS = (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1, 2, 5, 10, 20, 50,
                    100, 200, 500, 1000, 2000, 5000)


class StageFamily:
    """One histogram family over a fixed set of stages, bound once."""

    def __init__(self, name: str, help: str, stages: Sequence[str], cat: str):
        hist = metrics.histogram(name, help, labelnames=("stage",),
                                 buckets=STAGE_BUCKETS_MS)
        self.children = {s: hist.labels(stage=s) for s in stages}
        self.spans = {s: f"{cat}.{s}" for s in stages}
        self.cat = cat

    def tick(self):
        """A fresh per-tick timer, or the shared no-op when obs is off."""
        return _Tick(self) if ON.enabled else NO_TICK


class _Tick:
    """The stages of one tick: ``with tick(stage)`` runs one stage.

    Each run is one ``TRACER.begin``/``end`` span, and the span's own
    duration is the stage's time, so the histogram and the ring read the
    same two clock readings."""

    __slots__ = ("fam", "ms", "_stage", "_token")

    def __init__(self, fam: StageFamily):
        self.fam = fam
        self.ms: Dict[str, float] = {}

    def __call__(self, stage: str) -> "_Tick":
        self._stage = stage
        return self

    def __enter__(self):
        self._token = self.begin(self._stage)
        return self

    def __exit__(self, *exc):
        self.end(self._token)
        return False

    def begin(self, stage: str):
        """Start a stage (one that crosses an ``await`` too); ``end`` the
        token.  Its annotation, if any, stays open until then."""
        return stage, TRACER.begin(self.fam.spans[stage], self.fam.cat, annotate=True)

    def end(self, token) -> None:
        stage, span = token
        dur_us = TRACER.end(span)
        if dur_us is not None:
            self.add(stage, dur_us / 1e3)

    def add(self, stage: str, ms: float) -> None:
        """Add a time measured elsewhere (the daemon's executor round trip)."""
        self.ms[stage] = self.ms.get(stage, 0.0) + ms

    def observe(self) -> None:
        children = self.fam.children
        for stage, ms in self.ms.items():
            children[stage].observe(ms)


class _NoTick:
    __slots__ = ()

    def __call__(self, stage):
        return NOOP_SPAN

    def begin(self, stage):
        return None

    def end(self, token):
        pass

    def add(self, stage, ms):
        pass

    def observe(self):
        pass


NO_TICK = _NoTick()


_GC_PAUSE = metrics.histogram(
    "process_gc_pause_ms", "collector pauses while a daemon runs, by generation",
    labelnames=("generation",), buckets=STAGE_BUCKETS_MS)


class _GcPauses:
    """The ``gc.callbacks`` hook; installed while any daemon runs."""

    def __init__(self):
        self.children = tuple(_GC_PAUSE.labels(generation=g) for g in range(3))
        self.users = 0
        self._t0 = None
        self._span = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if not ON.enabled:
                self._t0 = None
                return
            if info["generation"] == 2:
                self._span = TRACER.begin("gc.collect", "process", annotate=True)
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.children[info["generation"]].observe(
                (time.perf_counter() - self._t0) * 1e3)
            self._t0 = None
            if self._span is not None:
                TRACER.end(self._span)
                self._span = None

    def install(self) -> None:
        if self.users == 0:
            gc.callbacks.append(self)
        self.users += 1

    def remove(self) -> None:
        self.users -= 1
        if self.users == 0:
            gc.callbacks.remove(self)


GC_PAUSES = _GcPauses()

"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Reads the file with ``jax.profiler.ProfileData`` and nothing else:

* device events: the planes named ``/device:<accelerator>:<i>``, their
  ``XLA Modules`` line (one event per program run) and ``XLA Ops`` line
  (one event per operation, kernels included);
* host spans: every event on the ``/host:CPU`` plane, among them the
  ``TraceAnnotation`` spans of the program and of the benchmark.

From them: the busy time (the union of the module intervals, or of the op
intervals where a plane has no module line) averaged over the devices,
device time by module or op name, and the idle gaps between busy
intervals, each named by the innermost host span that covers its middle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import heapq
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]   # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start: int   # ns
    end: int     # ns


@dataclasses.dataclass
class Trace:
    """Events of one traced window, in nanoseconds of one clock."""

    modules: Dict[str, List[Event]]   # device plane -> program runs
    ops: Dict[str, List[Event]]       # device plane -> operations
    host: List[Event]                 # host spans, every thread

    @property
    def devices(self) -> List[str]:
        return sorted(set(self.modules) | set(self.ops))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CUSTOM")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dest = modules.setdefault(plane.name, [])
                elif line.name == "XLA Ops":
                    dest = ops.setdefault(plane.name, [])
                else:
                    continue
                dest.extend(Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events)
    return Trace(modules=modules, ops=ops, host=host)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted disjoint cover of the given intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(ivs: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def busy_intervals(tr: Trace, device: str, lo: int, hi: int) -> List[Interval]:
    evs = tr.modules.get(device) or tr.ops.get(device) or []
    return _clip(union((e.start, e.end) for e in evs), lo, hi)


def busy_seconds(tr: Trace, lo: int, hi: int) -> float:
    """Seconds in [lo, hi) in which a program ran, averaged over the devices."""
    devs = tr.devices
    if not devs:
        return 0.0
    total = sum(e - s for d in devs for s, e in busy_intervals(tr, d, lo, hi))
    return total / len(devs) / 1e9


def time_by_name(events_by_device: Dict[str, List[Event]], lo: int, hi: int,
                 contains: str = "") -> Dict[str, Tuple[float, int]]:
    """name -> (seconds, count) of events whose name holds ``contains``,
    clipped to [lo, hi), summed over the devices."""
    acc: Dict[str, list] = collections.defaultdict(lambda: [0.0, 0])
    for evs in events_by_device.values():
        for e in evs:
            if contains in e.name and e.end > lo and e.start < hi:
                a = acc[e.name]
                a[0] += (min(e.end, hi) - max(e.start, lo)) / 1e9
                a[1] += 1
    return {k: (v[0], v[1]) for k, v in acc.items()}


def total_time(events_by_device: Dict[str, List[Event]], lo: int, hi: int,
               contains: str) -> Tuple[float, int]:
    """(seconds, count) of every event whose name holds ``contains``."""
    t = time_by_name(events_by_device, lo, hi, contains)
    return sum(v[0] for v in t.values()), sum(v[1] for v in t.values())


def idle_gaps(tr: Trace, lo: int, hi: int, top: int = 10) -> List[Tuple[str, float]]:
    """Device idle seconds in [lo, hi), summed by the innermost host span
    covering each gap's middle (first device; "no host span" where none)."""
    devs = tr.devices
    if not devs:
        return []
    busy = busy_intervals(tr, devs[0], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    # sweep the gaps' middles in order against the host spans sorted by
    # start; a max-heap on start holds the spans begun so far, and one that
    # ended before a middle ends before every later middle too, so it is
    # dropped for good: the heap's top is then the latest-begun span that
    # covers the middle, the innermost one
    host = sorted(tr.host, key=lambda e: e.start)
    acc: Dict[str, float] = collections.defaultdict(float)
    heap: list = []
    j = 0
    for s, e in sorted(gaps, key=lambda g: (g[0] + g[1]) // 2):
        mid = (s + e) // 2
        while j < len(host) and host[j].start <= mid:
            heapq.heappush(heap, (-host[j].start, j))
            j += 1
        while heap and host[heap[0][1]].end < mid:
            heapq.heappop(heap)
        name = host[heap[0][1]].name if heap else "no host span"
        acc[name] += (e - s) / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:top]


def top_ops(tr: Trace, lo: int, hi: int, top: int = 10) -> List[Tuple[str, float]]:
    """The device operations that took the most time, averaged over devices."""
    t = time_by_name(tr.ops or tr.modules, lo, hi)
    ndev = max(len(tr.devices), 1)
    return sorted(((k, v[0] / ndev) for k, v in t.items()), key=lambda kv: -kv[1])[:top]

"""Load generators: an open loop and a closed loop over an async ``submit``.

Open loop: arrivals at fixed offsets from the window's start, independent
of service (a Poisson schedule drawn from the seed).  One coroutine walks
the schedule: each time it wakes it submits every arrival that is due, so
no coroutine sleeps per arrival.  A request is timed from its due instant,
not from its submit, so a late generator or a stalled loop counts as
waiting; how late each submit ran is recorded as ``late_s``.

Closed loop: ``clients`` coroutines each submit a request and wait for its
answer before the next one, until the window closes.  A request is timed
from its submit.

Both return a ``Log``: per request its index, due or submit time, finish
time, and either its answer or the exception it raised.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Awaitable, Callable, List, Optional

import numpy as np


def poisson_offsets(rate_per_s: float, seconds: float, rng: np.random.Generator,
                    on_s: float = 0.0, off_s: float = 0.0) -> np.ndarray:
    """Sorted arrival offsets in [0, seconds) of a Poisson process of the
    given rate, conditioned on its expected count: round(rate * on-time)
    uniform instants of the on-time.  Every seed then offers the same number
    of arrivals, in a different order of gaps.  With ``on_s`` and ``off_s``
    both above 0 the window is on/off phases, on first, and arrivals come
    only in the on phases; otherwise it is on throughout."""
    if on_s <= 0 or off_s <= 0:
        return np.sort(rng.uniform(0.0, seconds, size=int(round(rate_per_s * seconds))))
    period = on_s + off_s
    full = int(seconds // period)
    on_time = full * on_s + min(on_s, seconds - full * period)
    t = np.sort(rng.uniform(0.0, on_time, size=int(round(rate_per_s * on_time))))
    phase = np.floor(t / on_s)
    return phase * period + (t - phase * on_s)


@dataclasses.dataclass
class Log:
    """What one window did, request by request (times from time.monotonic)."""

    t0: float                       # window start
    index: List[int] = dataclasses.field(default_factory=list)      # payload index
    start: List[float] = dataclasses.field(default_factory=list)    # due (open) or submit (closed)
    late_s: List[float] = dataclasses.field(default_factory=list)   # submit - due (open loop)
    finish: List[float] = dataclasses.field(default_factory=list)
    answer: List[Optional[np.ndarray]] = dataclasses.field(default_factory=list)
    error: List[Optional[BaseException]] = dataclasses.field(default_factory=list)
    pending: int = 0                # requests with no outcome when the loop gave up

    def record(self, i: int, start: float, fut_result, err) -> None:
        self.index.append(i)
        self.start.append(start)
        self.finish.append(time.monotonic())
        self.answer.append(fut_result)
        self.error.append(err)

    @property
    def end(self) -> float:
        return max(self.finish) if self.finish else self.t0


async def _one(log: Log, submit, i: int, payload, start: float) -> None:
    try:
        ans = await submit(payload)
    except asyncio.CancelledError:
        raise
    except Exception as e:  # shed or failed: the request's outcome, kept
        log.record(i, start, None, e)
        return
    log.record(i, start, ans, None)


async def open_loop(submit: Callable[[np.ndarray], Awaitable], payloads: List[np.ndarray],
                    offsets: np.ndarray, grace_s: float = 60.0) -> Log:
    """Submit ``payloads[i]`` at ``t0 + offsets[i]``; wait for every answer,
    at most ``grace_s`` past the last arrival."""
    loop = asyncio.get_running_loop()
    log = Log(t0=time.monotonic())
    # only unfinished requests are held, so the generator adds no garbage
    # that grows with the window for the collector to walk
    pending: set = set()
    k = 0
    total = len(payloads)
    while k < total:
        now = time.monotonic()
        while k < total and log.t0 + offsets[k] <= now:
            due = log.t0 + float(offsets[k])
            log.late_s.append(now - due)
            task = loop.create_task(_one(log, submit, k, payloads[k], due))
            pending.add(task)
            task.add_done_callback(pending.discard)
            k += 1
        if k < total:
            await asyncio.sleep(max(log.t0 + float(offsets[k]) - time.monotonic(), 0.0))
    await _settle(log, list(pending), grace_s)
    return log


async def closed_loop(submit: Callable[[np.ndarray], Awaitable], payload: Callable[[int, int], tuple],
                      clients: int, seconds: float, grace_s: float = 60.0) -> Log:
    """``clients`` callers, each submitting ``payload(client, k)`` -> (index,
    array) for its k-th request and waiting for the answer, while the window
    is open."""
    log = Log(t0=time.monotonic())
    t_end = log.t0 + seconds

    async def client(c: int) -> None:
        k = 0
        while time.monotonic() < t_end:
            i, arr = payload(c, k)
            await _one(log, submit, i, arr, time.monotonic())
            k += 1

    loop = asyncio.get_running_loop()
    tasks = [loop.create_task(client(c)) for c in range(clients)]
    await _settle(log, tasks, seconds + grace_s)
    return log


async def _settle(log: Log, tasks: list, grace_s: float) -> None:
    done, pending = await asyncio.wait(tasks, timeout=grace_s) if tasks else (set(), set())
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.gather(*pending, return_exceptions=True)
    for t in done:
        t.result()
    log.pending = len(pending)

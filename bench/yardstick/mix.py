"""The general traffic generator: a traffic mix is data, and this reads it.

A mix is ``bench/traffic/<name>.json``.  Every cell that serves queries is
one of these files and nothing else; its keys:

  driver              the module of ``bench/drivers/`` that runs the mix
  request_pairs       pairs in one request
  pool_pairs          pairs drawn from the seed, cut into blocks of
                      ``request_pairs``; each request sends one block, so a
                      window that sends more than the pool sends blocks again.
                      Absent in an open loop: one block per arrival
  reachable_share     share of the pool drawn reachable, one BFS per source,
                      at most ``per_source`` (default 8) targets a source, the
                      rest unreachable from the same sources (the paper's
                      equal set is 0.5).  Absent: pairs uniform over all
                      vertices (the paper's random set)
  zipf_s              which block a request sends: 0 (default) the blocks in
                      turn; above 0 Zipf(s) over a ranking of the blocks
                      drawn from the seed
  hot_shift_requests  with ``zipf_s``: the ranking drawn anew every this many
                      requests of a caller (0, the default: never)

and the arrivals, one of:

  clients             closed loop: this many callers, each sending a request
                      and waiting for its answer before the next
  arrivals_per_s      open loop: Poisson arrivals at this rate, the count
                      fixed by the rate and the window; with ``burst_on_s``
                      and ``burst_off_s`` they come only in the on phases,
                      at this rate there

Every seed draws the same number of pairs and requests; the seed changes
which pairs and the order, not the amount of work.
"""
from __future__ import annotations

from typing import Callable, Tuple

import numpy as np

from bench.yardstick import loops, samplers
from bench.yardstick.reach import Adjacency


def blocks(spec: dict, adj: Adjacency, size: int, rng: np.random.Generator) -> np.ndarray:
    """int32[size // request_pairs, request_pairs, 2]: the mix's pool of
    pairs, cut into requests."""
    share = spec.get("reachable_share")
    if share is None:
        pairs = samplers.uniform_pairs(adj.n, size, rng)
    else:
        pairs = samplers.equal_pairs(adj, size, rng, per_source=int(spec.get("per_source", 8)),
                                     share=float(share))
    per = int(spec["request_pairs"])
    return pairs[:pairs.shape[0] // per * per].reshape(-1, per, 2)


class Popularity:
    """Which block a caller's k-th request sends: ``block(caller, k)``."""

    def __init__(self, spec: dict, n_blocks: int, callers: int, seed: int):
        self.n_blocks = n_blocks
        self.callers = callers
        self.seed = seed
        self.zipf_s = float(spec.get("zipf_s", 0.0))
        self.shift = int(spec.get("hot_shift_requests", 0))
        if self.zipf_s > 0:
            w = 1.0 / np.arange(1, n_blocks + 1, dtype=np.float64) ** self.zipf_s
            self.cdf = np.cumsum(w / w.sum())
            self.draws = [np.random.default_rng([seed, 20, c]) for c in range(callers)]
            self.rankings: dict = {}     # caller -> (epoch, ranking)

    def _ranking(self, caller: int, epoch: int) -> np.ndarray:
        held = self.rankings.get(caller)
        if held is None or held[0] != epoch:
            held = self.rankings[caller] = (epoch, np.random.default_rng(
                [self.seed, 21, caller, epoch]).permutation(self.n_blocks))
        return held[1]

    def block(self, caller: int, k: int) -> int:
        """A caller's requests are asked for in order, k = 0, 1, 2, ..."""
        if self.zipf_s <= 0:
            return (caller * self.n_blocks // self.callers + k) % self.n_blocks
        rank = min(int(np.searchsorted(self.cdf, self.draws[caller].random())), self.n_blocks - 1)
        epoch = k // self.shift if self.shift > 0 else 0
        return int(self._ranking(caller, epoch)[rank])


def requests(spec: dict, adj: Adjacency, seconds: float,
             rng: np.random.Generator, seed: int) -> Tuple[Callable, Callable, list]:
    """(drive, pairs_of, warm) for one window of the mix.

    ``drive(submit)`` is the loop's coroutine; it returns a ``loops.Log``
    whose ``index`` entries ``pairs_of`` turns back into the pairs sent.
    ``warm`` is a few requests' pairs for a warm pass before the window."""
    per = int(spec["request_pairs"])
    if "clients" in spec:
        clients = int(spec["clients"])
        pool = blocks(spec, adj, int(spec["pool_pairs"]), rng)
        pop = Popularity(spec, pool.shape[0], clients, seed)

        def payload(c: int, k: int):
            i = pop.block(c, k)
            return i, pool[i]

        def drive(submit):
            return loops.closed_loop(submit, payload, clients, seconds)

        return drive, pool.__getitem__, [pool[c % pool.shape[0]] for c in range(clients)]
    offsets = loops.poisson_offsets(float(spec["arrivals_per_s"]), seconds, rng,
                                    float(spec.get("burst_on_s", 0)),
                                    float(spec.get("burst_off_s", 0)))
    size = int(spec.get("pool_pairs", 0)) or offsets.size * per
    pool = blocks(spec, adj, size, rng)
    pop = Popularity(spec, pool.shape[0], 1, seed)
    payloads = [pool[pop.block(0, k)] for k in range(offsets.size)]

    def drive(submit):
        return loops.open_loop(submit, payloads, offsets)

    return drive, payloads.__getitem__, payloads[:max(1, min(len(payloads), 4096 // per))]

"""Plain reachability by breadth-first search: the benchmark's reference.

Independent of the program: its own adjacency from the edge arrays, and a
level-synchronous BFS from many sources at once.  A pair ``(s, v)`` is kept
as the key ``s * n + v``; the closure of a set of sources is the sorted key
array of every vertex each source reaches by a path of one edge or more.
A vertex reaches itself by definition (``reaches`` answers ``u == v`` true).
"""
from __future__ import annotations

import numpy as np


class Adjacency:
    """Out-neighbour lists of a directed graph, from its edge arrays."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        order = np.argsort(src, kind="stable")
        self.n = int(n)
        self.indices = dst[order]
        self.indptr = np.zeros(self.n + 1, dtype=np.int64)
        np.cumsum(np.bincount(src, minlength=self.n), out=self.indptr[1:])

    def degree(self) -> np.ndarray:
        return np.diff(self.indptr)

    def expand(self, owner: np.ndarray, at: np.ndarray):
        """Every (owner, w) with an edge at -> w, for parallel arrays."""
        lo, hi = self.indptr[at], self.indptr[at + 1]
        cnt = hi - lo
        total = int(cnt.sum())
        if total == 0:
            return owner[:0], at[:0]
        starts = np.repeat(lo - np.cumsum(cnt) + cnt, cnt)
        return np.repeat(owner, cnt), self.indices[starts + np.arange(total)]


def closure(adj: Adjacency, sources: np.ndarray) -> np.ndarray:
    """Sorted unique keys ``s * n + v`` of every v reachable from each source."""
    n = np.int64(adj.n)
    s = np.unique(np.asarray(sources, dtype=np.int64))
    seen = np.empty(0, dtype=np.int64)
    own, at = adj.expand(s, s)
    while own.size:
        keys = np.unique(own * n + at)
        keys = keys[~np.isin(keys, seen, assume_unique=True)]
        if not keys.size:
            break
        seen = np.union1d(seen, keys)
        own, at = adj.expand(keys // n, keys % n)
    return seen


def reaches(keys: np.ndarray, n: int, u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """bool[k]: does u[i] reach v[i], given a closure that covers every u[i]."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    q = u * np.int64(n) + v
    pos = np.searchsorted(keys, q)
    hit = (pos < keys.size) & (keys[np.minimum(pos, max(keys.size - 1, 0))] == q) \
        if keys.size else np.zeros(q.shape, dtype=bool)
    return hit | (u == v)


def topo_levels(adj: Adjacency) -> np.ndarray:
    """int64[n] longest-path level of each vertex of a DAG (sources at 0)."""
    indeg = np.bincount(adj.indices, minlength=adj.n)
    level = np.zeros(adj.n, dtype=np.int64)
    frontier = np.nonzero(indeg == 0)[0]
    d = 0
    while frontier.size:
        level[frontier] = d
        _, nxt = adj.expand(frontier, frontier)
        np.subtract.at(indeg, nxt, 1)
        frontier = np.unique(nxt[indeg[nxt] == 0])
        d += 1
    return level

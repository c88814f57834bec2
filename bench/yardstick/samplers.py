"""Query pairs of the paper's two query sets (arXiv:1305.0502, section 6.1).

``uniform_pairs`` is the "random" set: pairs drawn uniformly over all
vertices.  ``equal_pairs`` is the "equal" set: half reachable (another
``share`` may be asked for).  It takes sources among the vertices with an
out-edge, one BFS each (all at once, through ``reach.closure``), keeps at
most ``per_source`` of the vertices each one reaches, and pairs the same
sources with vertices they do not reach for the rest; the same rule as
``repro.graph.reach.sample_reachability_batch``.
"""
from __future__ import annotations

import numpy as np

from bench.yardstick.reach import Adjacency, closure


def uniform_pairs(n: int, count: int, rng: np.random.Generator) -> np.ndarray:
    return rng.integers(0, n, size=(count, 2)).astype(np.int32)


def equal_pairs(adj: Adjacency, count: int, rng: np.random.Generator,
                per_source: int = 8, share: float = 0.5) -> np.ndarray:
    """int32[count, 2]: int(count * share) reachable pairs (as many as the
    sources reach) and the rest unreachable, in a random order."""
    n = adj.n
    with_out = np.nonzero(adj.degree() > 0)[0]
    n_pos = int(count * share)
    n_src = max(2 * n_pos // per_source, 1)
    while True:
        src = np.unique(rng.choice(with_out, size=min(n_src, with_out.size), replace=False))
        keys = closure(adj, src)
        # at most per_source reachable vertices per source, chosen at random
        keys = keys[rng.permutation(keys.size)]
        owner = keys // n
        order = np.argsort(owner, kind="stable")
        keys, owner = keys[order], owner[order]
        first = np.searchsorted(owner, owner)
        keys = keys[np.arange(keys.size) - first < per_source]
        if keys.size >= n_pos or n_src >= with_out.size:
            break
        n_src *= 2
    pos = keys[rng.choice(keys.size, size=min(n_pos, keys.size), replace=False)]
    all_keys = closure(adj, src)
    neg = np.empty(0, dtype=np.int64)
    while neg.size < count - pos.size:
        k = 2 * (count - pos.size - neg.size) + 16
        u = rng.choice(src, size=k)
        v = rng.integers(0, n, size=k)
        cand = u * np.int64(n) + v
        ok = (u != v) & ~np.isin(cand, all_keys)
        neg = np.concatenate([neg, cand[ok]])
    keys = np.concatenate([pos, neg[: count - pos.size]])
    keys = keys[rng.permutation(keys.size)]
    return np.stack([keys // n, keys % n], axis=1).astype(np.int32)

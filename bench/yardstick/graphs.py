"""The benchmark's own copy of the synthetic graph families.

Copied from ``repro.graph.generators`` (random_dag, layered_dag, tree_dag
and the Table-1 size rule of ``paper_dataset_analogue``, less its floors),
so that a change to the program's generators cannot change the data the
benchmark serves.
Each function returns ``(n, src, dst)`` edge arrays; the benchmark hands
them to the program's ``from_edges`` and builds its own adjacency for the
reference from the same arrays.  ``bench/tests/test_yardstick.py`` checks
that the program's generators still give byte-identical graphs.
"""
from __future__ import annotations

import numpy as np


def random_dag(n: int, m: int, seed: int):
    """Uniform random DAG: m edges oriented low->high under a random permutation."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n).astype(np.int64)
    k = int(m * 1.3) + 16
    a = rng.integers(0, n, size=k)
    b = rng.integers(0, n, size=k)
    mask = a != b
    a, b = a[mask], b[mask]
    ra, rb = perm[a], perm[b]
    src = np.where(ra < rb, a, b)
    dst = np.where(ra < rb, b, a)
    return n, src[:m], dst[:m]


def layered_dag(n: int, avg_out: float, seed: int, n_layers: int = 12, skip: float = 0.15):
    """Citation-style DAG: edges point to earlier layers, a ``skip`` share
    of them jumping far back (long-range citations)."""
    rng = np.random.default_rng(seed)
    layer = rng.integers(0, n_layers, size=n)
    order = np.argsort(layer, kind="stable")
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n)
    m = int(n * avg_out)
    src = rng.integers(0, n, size=m)
    lo = np.maximum(rank[src] * (1.0 - np.where(rng.random(m) < skip, 0.9, 0.3)), 0)
    dst_rank = (lo + rng.random(m) * np.maximum(rank[src] - lo, 1)).astype(np.int64)
    dst_rank = np.minimum(dst_rank, np.maximum(rank[src] - 1, 0))
    dst = order[dst_rank]
    keep = rank[src] > rank[dst]
    return n, src[keep], dst[keep]


def tree_dag(n: int, branching: int, extra_frac: float, seed: int):
    """Ontology-style shallow tree plus a few cross edges."""
    rng = np.random.default_rng(seed)
    src = [np.maximum((np.arange(1, n) - 1) // branching, 0)]
    dst = [np.arange(1, n)]
    n_extra = int(n * extra_frac)
    if n_extra:
        a = rng.integers(0, n, size=n_extra)
        b = rng.integers(0, n, size=n_extra)
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        keep = lo != hi
        src.append(lo[keep])
        dst.append(hi[keep])
    return n, np.concatenate(src), np.concatenate(dst)


def table1_edges(n_table: int, m_table: int, family: str, seed: int, scale: float = 1.0):
    """Edges of the analogue of a Table-1 graph of ``n_table`` vertices and
    ``m_table`` edges, by the size rule of ``paper_dataset_analogue`` less
    its floors: that rule draws at least n / 2 edges, so it made citeseer
    (m / n = 0.45) 11% denser than the source.  Here the family draws the
    source's own edge count; where the floors do not bind, the graph is
    the program's, byte for byte."""
    n = max(int(n_table * scale), 64)
    m = max(int(m_table * scale), 1)
    if family == "sparse":
        return random_dag(n, m, seed)
    if family == "layered":
        return layered_dag(n, m / n, seed)
    if family == "tree":
        branching = max(int(round(n / max(m - n, 1))) if m > n else 8, 2)
        return tree_dag(n, min(branching, 64), max(m / n - 1.0, 0.02), seed)
    raise ValueError(f"unknown graph family {family!r}")


def relabel(n: int, src: np.ndarray, dst: np.ndarray, seed: int):
    """The same graph under vertex ids permuted from ``seed``, without
    duplicate edges or self-loops.

    The permutation keeps the relative order of ids among vertices of equal
    degree product (out + 1) * (in + 1), the rank of section 5.2 of the
    paper, whose ties are broken by id: the rank order, and with it the
    labelling work, is then the same for every seed, while every array the
    program indexes by vertex is laid out anew."""
    key = np.unique(np.asarray(src, np.int64) * n + np.asarray(dst, np.int64))
    src, dst = key // n, key % n
    keep = src != dst
    src, dst = src[keep], dst[keep]
    score = (np.bincount(src, minlength=n) + 1) * (np.bincount(dst, minlength=n) + 1)
    by_class = np.lexsort((np.arange(n), score))          # classes, ids ascending in each
    drawn = np.random.default_rng([seed, 3]).permutation(n)[by_class]
    new = np.empty(n, dtype=np.int64)
    new[by_class] = drawn[np.lexsort((drawn, score[by_class]))]
    return n, new[src], new[dst]

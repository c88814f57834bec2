"""The least bytes a kernel's work has to move, whatever implements it.

A roofline share is (these bytes / the chip's HBM bandwidth) over the
kernel's device time from the trace.  The counts depend on no layout,
padding or entry width of the program, so a denser layout cannot push a
share over 100%.
"""
from __future__ import annotations

import math

import numpy as np


def id_bytes(count: int) -> int:
    """Bytes that hold one of ``count`` distinct ids."""
    return max(math.ceil(math.log2(max(count, 2)) / 8), 1)


def intersect_bytes(out_len: np.ndarray, in_len: np.ndarray, u: np.ndarray, v: np.ndarray,
                    n_hops: int, n_vertices: int) -> int:
    """Label intersection of the queries (u, v) that reach the device: both
    label rows at ``id_bytes(n_hops)`` a hop, the two query ids, and one
    verdict bit each."""
    entries = int(out_len[u].astype(np.int64).sum() + in_len[v].astype(np.int64).sum())
    q = int(np.asarray(u).size)
    return entries * id_bytes(n_hops) + 2 * q * id_bytes(n_vertices) + math.ceil(q / 8)


def needs_labels(u: np.ndarray, v: np.ndarray, out_len: np.ndarray, in_len: np.ndarray,
                 level: np.ndarray) -> np.ndarray:
    """bool[k]: queries that no structural fact decides (distinct ids, both
    label rows non-empty, u's topological level below v's): the ones that
    reach the device."""
    return (u != v) & (out_len[u] > 0) & (in_len[v] > 0) & (level[u] < level[v])

"""Top-level oracle API: arbitrary digraphs (cycles allowed) in one call.

The paper (§2) assumes SCC condensation as a preprocessing step; this is
that step made first-class:

    oracle = build_oracle(graph)            # graph may have cycles
    oracle.query(u, v)                      # original vertex ids
    oracle.serve(queries)                   # batched engine path
    oracle.serve(queries, backend="kernel") # pick the intersection backend

Serving is owned by a ``repro.serve.QueryEngine`` (prefilters + length
bucketing + pluggable backends); the condensation's topological levels feed
the engine's level prefilter.
"""
from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import numpy as np

from repro.core.distribution import distribution_labeling
from repro.core.hierarchy import hierarchical_labeling
from repro.core.oracle import ReachabilityOracle
from repro.graph.csr import CSRGraph
from repro.graph.scc import COMP_ORDER, condense_to_dag
from repro.serve.engine import QueryEngine
from repro.serve.prefilter import topo_levels


@dataclasses.dataclass(frozen=True)
class CondensedOracle:
    """Reachability oracle over the SCC condensation of a digraph.

    Queries take ORIGINAL vertex ids; two vertices in the same SCC reach
    each other by definition (the engine's same-id prefilter answers them).
    """

    oracle: ReachabilityOracle
    comp: np.ndarray  # int32[n_original] -> condensation vertex id
    engine: QueryEngine

    @property
    def total_label_size(self) -> int:
        return self.oracle.total_label_size

    def query(self, u: int, v: int) -> bool:
        return self.engine.query(int(u), int(v))

    def serve(self, queries: np.ndarray, backend: Optional[str] = None,
              deadline: Optional[float] = None) -> np.ndarray:
        """Batched engine path. queries: int[B, 2] original ids -> bool[B].

        The original->condensation mapping happens inside the engine through
        its ``comp_source`` hook (reading this oracle's current comp array),
        so the same-SCC short-circuit can never act on a stale cached copy
        when the condensation is maintained dynamically.  ``deadline`` is
        the daemon's absolute latency budget (see
        ``QueryEngine.query_batch``)."""
        return self.engine.query_batch(np.asarray(queries), backend=backend,
                                       deadline=deadline)


def build_oracle(
    g: CSRGraph,
    method: Literal["distribution", "hierarchical"] = "distribution",
    backend: str = "auto",
    mesh=None,
    bucketing: bool = True,
    **kwargs,
) -> CondensedOracle:
    """Condense SCCs, label with DL (default) or HL, wire up the serve engine."""
    dag, comp = condense_to_dag(g)
    if method == "distribution":
        oracle = distribution_labeling(dag, **kwargs)
    elif method == "hierarchical":
        oracle = hierarchical_labeling(dag, **kwargs)
    else:
        raise ValueError(method)
    engine = QueryEngine(
        oracle,
        backend=backend,
        level=topo_levels(dag),
        mesh=mesh,
        bucketing=bucketing,
        # degradation ladder bottom rung: the condensation DAG the labels
        # index, so corrupted/missing rows degrade to exact online search
        fallback_graph=dag,
    )
    co = CondensedOracle(oracle=oracle, comp=comp, engine=engine)
    # queries reach the engine in original ids; the engine reads the comp
    # array through the oracle at call time (never a private cached copy)
    engine.comp_source = lambda: co.comp
    return co


def oracle_from_snapshot(
    g: CSRGraph,
    path: str,
    mode: Literal["strict", "quarantine"] = "strict",
    backend: str = "auto",
    mesh=None,
    bucketing: bool = True,
) -> CondensedOracle:
    """Cold-start serving: wire a persisted label snapshot to ``g``'s
    condensation instead of rebuilding the index.

    ``mode="strict"`` raises ``persist.CorruptSnapshotError`` on any
    checksum mismatch; ``mode="quarantine"`` loads anyway, zeroes the
    corrupt row blocks, and arms the engine's quarantine masks so queries
    touching them degrade to exact online search over the condensation DAG
    (throughput cost, never a wrong verdict).

    The caller vouches that ``path`` was saved from THIS graph's
    condensation (``save_oracle(path, co.oracle)``); a snapshot of a
    different graph fails the cheap shape check here and answers garbage
    past it — persist snapshots are content-checksummed, not graph-keyed.
    A snapshot whose rows are indexed in another SCC id order (any saved
    before the order was recorded) is refused with ``CorruptSnapshotError``.
    """
    from repro.persist import load_oracle

    if mode not in ("strict", "quarantine"):
        raise ValueError(f"mode must be strict|quarantine, got {mode!r}")
    dag, comp = condense_to_dag(g)
    report = None
    if mode == "strict":
        oracle = load_oracle(path, strict=True, comp_order=COMP_ORDER)
    else:
        oracle, report = load_oracle(path, strict=False, comp_order=COMP_ORDER)
    if oracle.n != dag.n:
        raise ValueError(
            f"snapshot at {path} indexes {oracle.n} vertices but the "
            f"graph's condensation has {dag.n} — wrong snapshot for this graph")
    engine = QueryEngine(
        oracle, backend=backend, level=topo_levels(dag), mesh=mesh,
        bucketing=bucketing, fallback_graph=dag,
    )
    co = CondensedOracle(oracle=oracle, comp=comp, engine=engine)
    engine.comp_source = lambda: co.comp
    if report is not None and not report.clean:
        engine.set_quarantine(report.quarantine_out, report.quarantine_in)
    return co

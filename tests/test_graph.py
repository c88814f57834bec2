"""Graph substrate unit tests."""
import numpy as np
import pytest

from repro.graph.csr import (
    CSRGraph,
    from_edges,
    is_dag,
    topo_levels,
    topological_order,
)
from repro.graph.generators import (
    chain_dag,
    layered_dag,
    paper_dataset_analogue,
    random_dag,
    scale_free_dag,
    tree_dag,
)
from repro.graph.reach import (
    bfs_levels,
    reachable_set,
    reaches_bit,
    sample_query_workload,
    sample_reachability_batch,
    transitive_closure_bits,
)
from repro.graph.scc import condense_to_dag, tarjan_scc


def test_csr_roundtrip():
    g = from_edges(5, [0, 0, 1, 3], [1, 2, 2, 4])
    assert g.n == 5 and g.m == 4
    assert list(g.out_neighbors(0)) == [1, 2]
    src, dst = g.edges()
    g2 = from_edges(5, src, dst)
    assert (g2.indptr == g.indptr).all() and (g2.indices == g.indices).all()


def test_reverse_degrees():
    g = random_dag(100, 300, seed=1)
    r = g.reverse()
    assert (g.in_degree() == r.out_degree()).all()
    assert g.m == r.m
    # double reverse == identity (as edge set)
    rr = r.reverse()
    s1 = set(zip(*g.edges()))
    s2 = set(zip(*rr.edges()))
    assert s1 == s2


def test_generators_are_dags():
    for g in [
        random_dag(200, 600, seed=0),
        layered_dag(200, 2.5, seed=1),
        tree_dag(200, 4, seed=2),
        scale_free_dag(200, 3.0, seed=3),
        chain_dag(200, 4, seed=4),
        paper_dataset_analogue("amaze", scale=0.5),
    ]:
        assert is_dag(g)
        topo = topological_order(g)
        pos = np.empty(g.n, dtype=np.int64)
        pos[topo] = np.arange(g.n)
        src, dst = g.edges()
        assert (pos[src] < pos[dst]).all()


def test_topo_levels_longest_path():
    """Vectorized topo levels == the scalar longest-path relaxation, and
    every edge strictly increases the level (the serve-filter invariant)."""
    for g in (random_dag(200, 600, seed=1), tree_dag(150, branching=3, seed=2),
              chain_dag(120, seed=3)):
        level = topo_levels(g)
        expect = np.zeros(g.n, dtype=np.int32)
        for v in topological_order(g):
            for w in g.out_neighbors(v):
                expect[w] = max(expect[w], expect[v] + 1)
        assert np.array_equal(level, expect)
        src = np.repeat(np.arange(g.n), np.diff(g.indptr))
        assert (level[src] < level[g.indices]).all()
    with pytest.raises(ValueError):
        topo_levels(from_edges(3, [0, 1, 2], [1, 2, 0], dedup=False))


def test_scc_condensation():
    # two 3-cycles connected by an edge + isolated vertex
    src = [0, 1, 2, 3, 4, 5, 2]
    dst = [1, 2, 0, 4, 5, 3, 3]
    g = from_edges(7, src, dst)
    dag, comp = condense_to_dag(g)
    assert dag.n == 3  # {0,1,2}, {3,4,5}, {6}
    assert is_dag(dag)
    assert comp[0] == comp[1] == comp[2]
    assert comp[3] == comp[4] == comp[5]
    assert comp[0] != comp[3] != comp[6]


def test_scc_condensation_keeps_vertex_order():
    """An acyclic graph condenses to itself; SCC ids follow each
    component's smallest vertex."""
    g = random_dag(200, 600, seed=3)
    dag, comp = condense_to_dag(g)
    assert np.array_equal(comp, np.arange(g.n))
    assert np.array_equal(dag.indptr, g.indptr) and np.array_equal(dag.indices, g.indices)
    cyc = from_edges(5, [4, 3, 1, 2], [3, 4, 2, 1])  # {1,2} and {3,4}
    _, comp = condense_to_dag(cyc)
    assert comp.tolist() == [0, 1, 1, 2, 2]


def test_reachability_batch_balance_and_truth():
    g = layered_dag(400, 1.5, seed=2)
    q, truth = sample_reachability_batch(g, 300, np.random.default_rng(0))
    assert q.shape == (300, 2) and 0.4 <= truth.mean() <= 0.6
    for (u, v), t in zip(q, truth):
        assert bool(reachable_set(g, int(u))[v]) == t


def test_tc_bits_vs_dfs():
    g = random_dag(150, 400, seed=2)
    tc = transitive_closure_bits(g)
    rng = np.random.default_rng(0)
    for u in rng.integers(0, g.n, 12):
        rs = reachable_set(g, int(u))
        for v in rng.integers(0, g.n, 25):
            assert reaches_bit(tc, int(u), int(v)) == bool(rs[v])


def test_bfs_levels_monotone():
    g = layered_dag(120, 2.0, seed=3)
    lv = bfs_levels(g, 0)
    src, dst = g.edges()
    for s, d in zip(src, dst):
        if lv[s] >= 0 and lv[d] >= 0:
            assert lv[d] <= lv[s] + 1


def test_query_workload_balance():
    g = random_dag(150, 500, seed=4)
    rng = np.random.default_rng(1)
    q, truth = sample_query_workload(g, 200, rng, equal=True)
    assert q.shape == (200, 2)
    assert 0.3 <= truth.mean() <= 0.7

"""The serve subsystem: cross-backend agreement, planner, prefilters, ranks.

The headline property: every QueryEngine backend returns bit-identical
answers to BFS ground truth — on random DAGs, cyclic digraphs (same-SCC
pairs included), and graphs with isolated vertices.
"""
import numpy as np
import pytest

from repro.core.api import build_oracle
from repro.core.distribution import distribution_labeling
from repro.graph.csr import from_edges
from repro.graph.generators import layered_dag, random_dag, tree_dag
from repro.serve.engine import BACKENDS, QueryEngine, select_backend
from repro.serve.planner import plan_batch, tier_widths
from repro.serve.prefilter import apply_prefilters, topo_levels

HOST_BACKENDS = ("host", "dense", "kernel")


def _truth_matrix(n, src, dst):
    """bool[n, n] reachability (reflexive) by BFS from each vertex."""
    adj = [[] for _ in range(n)]
    for s, d in zip(src, dst):
        adj[int(s)].append(int(d))
    out = np.zeros((n, n), dtype=bool)
    for u in range(n):
        seen = {u}
        stack = [u]
        while stack:
            x = stack.pop()
            for w in adj[x]:
                if w not in seen:
                    seen.add(w)
                    stack.append(w)
        out[u, list(seen)] = True
    return out


def _graph_families(rng):
    """(name, graph) pairs spanning DAGs, cycles, and isolated vertices."""
    fams = []
    fams.append(("random_dag", random_dag(70, 200, seed=1)))
    fams.append(("layered_dag", layered_dag(80, avg_out=2.5, seed=2)))
    fams.append(("tree_dag", tree_dag(90, branching=4, seed=3)))
    # cyclic digraph: uniform random edges leave plenty of nontrivial SCCs
    n = 60
    src, dst = rng.integers(0, n, 170), rng.integers(0, n, 170)
    fams.append(("cyclic", from_edges(n, src, dst)))
    # sparse cyclic graph with isolated vertices (edges only touch the
    # first half of the id space)
    n = 80
    src, dst = rng.integers(0, n // 2, 60), rng.integers(0, n // 2, 60)
    fams.append(("isolated", from_edges(n, src, dst)))
    return fams


def test_cross_backend_agreement_with_bfs_truth(rng):
    """All engine backends == BFS ground truth, >= 10k queries, >= 3 families."""
    total = 0
    for name, g in _graph_families(rng):
        truth = _truth_matrix(g.n, *g.edges())
        oracle = build_oracle(g)
        # uniform pairs + forced diagonal/same-SCC pairs + corner ids
        q = rng.integers(0, g.n, size=(2200, 2)).astype(np.int32)
        diag = np.arange(g.n, dtype=np.int32)
        q = np.concatenate([q, np.stack([diag, diag], 1),
                            np.array([[0, g.n - 1], [g.n - 1, 0]], np.int32)])
        exp = truth[q[:, 0], q[:, 1]]
        for be in HOST_BACKENDS:
            pred = oracle.serve(q, backend=be)
            assert (pred == exp).all(), (name, be, int((pred != exp).sum()))
        total += q.shape[0]
    assert total >= 10_000


def test_hierarchical_method_cross_backend(rng):
    """HL-built oracles serve correctly too (the HL core inherits DL labels,
    which live in rank space — this guards the unrank at the seam)."""
    g = random_dag(150, 500, seed=0)
    truth = _truth_matrix(g.n, *g.edges())
    o = build_oracle(g, method="hierarchical", core_max=16)
    q = rng.integers(0, g.n, size=(4000, 2)).astype(np.int32)
    exp = truth[q[:, 0], q[:, 1]]
    for be in HOST_BACKENDS:
        pred = o.serve(q, backend=be)
        assert (pred == exp).all(), (be, int((pred != exp).sum()))


def test_engine_point_queries_match_batch(rng):
    g = random_dag(50, 140, seed=7)
    truth = _truth_matrix(g.n, *g.edges())
    o = build_oracle(g)
    for u in range(g.n):
        for v in range(g.n):
            assert o.query(u, v) == truth[u, v], (u, v)


def test_bucketing_matches_unbucketed(rng):
    g = layered_dag(150, avg_out=3.0, seed=11)
    o_b = build_oracle(g, bucketing=True)
    o_n = build_oracle(g, bucketing=False)
    q = rng.integers(0, g.n, size=(4000, 2)).astype(np.int32)
    for be in ("dense", "kernel"):
        a = o_b.serve(q, backend=be)
        b = o_n.serve(q, backend=be)
        assert (a == b).all(), be
    # bucketing actually engaged (at least one tier ran under the full width)
    assert o_b.engine.last_stats["tiers"], "no tiers ran"


def test_backend_selection():
    assert select_backend(None) in BACKENDS
    assert select_backend("auto") in ("dense", "kernel")
    assert select_backend("host") == "host"
    with pytest.raises(ValueError):
        select_backend("nope")
    with pytest.raises(ValueError):
        select_backend("sharded")  # no mesh


def test_planner_partitions_and_covers(rng):
    out_len = rng.integers(0, 40, 500).astype(np.int32)
    in_len = rng.integers(0, 40, 500).astype(np.int32)
    widths = tier_widths(out_len, in_len, 40)
    assert widths == sorted(widths) and widths[-1] >= 40
    q = rng.integers(0, 500, size=(3000, 2)).astype(np.int32)
    plan = plan_batch(q, out_len, in_len, widths)
    idx_all = np.concatenate([t.idx for t in plan.tiers])
    # exact partition of the batch
    assert np.array_equal(np.sort(idx_all), np.arange(3000))
    for t in plan.tiers:
        need = np.maximum(out_len[q[t.idx, 0]], in_len[q[t.idx, 1]])
        assert (need <= t.width).all()
        assert t.rows >= t.idx.size and (t.rows & (t.rows - 1)) == 0  # pow2 tile


def test_prefilters_sound(rng):
    g = random_dag(60, 150, seed=5)
    truth = _truth_matrix(g.n, *g.edges())
    o = distribution_labeling(g)
    level = topo_levels(g)
    q = rng.integers(0, g.n, size=(5000, 2)).astype(np.int32)
    pf = apply_prefilters(q, o.out_len, o.in_len, level)
    exp = truth[q[:, 0], q[:, 1]]
    # every decided answer is correct (soundness — never a wrong short-circuit)
    assert (pf.value[pf.decided] == exp[pf.decided]).all()
    # and the filters actually fire on a random workload
    assert pf.decided.sum() > 0


def test_rank_ordered_labels(rng):
    g = layered_dag(120, avg_out=2.5, seed=9)
    o = distribution_labeling(g)
    assert o.hop_rank is not None
    # rows are ascending in rank space (value-sorted == rank-sorted)
    for mat, lens in ((o.L_out, o.out_len), (o.L_in, o.in_len)):
        for v in range(g.n):
            row = mat[v, : lens[v]]
            assert (np.diff(row) > 0).all(), v
    # unrank round-trips to real vertex ids
    row = o.L_out[0, : o.out_len[0]]
    verts = o.unrank(row)
    assert ((verts >= 0) & (verts < g.n)).all()
    assert set(o.hop_rank[verts].tolist()) == set(row.tolist())


def test_sharded_backend_agreement():
    """Replicated + hop-sharded serving agree with truth on a multi-device
    host mesh (subprocess — the main process must keep 1 CPU device)."""
    import os
    import subprocess
    import sys

    snippet = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, numpy as np
from repro.core.distribution import distribution_labeling
from repro.graph.generators import random_dag
from repro.graph.reach import transitive_closure_bits, sample_query_workload
from repro.serve.engine import QueryEngine
mesh = jax.make_mesh((4, 2), ('data', 'model'))
g = random_dag(200, 520, seed=0)
o = distribution_labeling(g)
tc = transitive_closure_bits(g)
rng = np.random.default_rng(0)
q, truth = sample_query_workload(g, 100, rng, equal=True, tc=tc)
eng = QueryEngine(o, mesh=mesh, data_axes=('data',))
for be in ('sharded', 'sharded_hop'):
    pred = eng.query_batch(np.asarray(q), backend=be)
    assert (pred == truth).all(), be
    assert not any(eng.degradation.values()), (be, eng.degradation)
# the override's layout is resharded once per label store, not per batch
lo_hop = eng._resharded[3][0]
eng.query_batch(np.asarray(q), backend='sharded_hop')
assert eng._resharded[3][0] is lo_hop
# budget-truncated labels land in the mesh layout too, never on one device
from repro.serve.budget import BudgetController, label_bytes
hop = QueryEngine(o, backend='sharded_hop', mesh=mesh, data_axes=('data',),
                  fallback_graph=g)
BudgetController(hop, budget_bytes=label_bytes(o) // 2)
lo = hop._budget_view[1]
assert lo.sharding.spec == jax.sharding.PartitionSpec(None, 'model'), lo.sharding
assert (hop.query_batch(np.asarray(q)) == truth).all()
print('SHARDED_ENGINE_OK')
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # inherit the environment (JAX_PLATFORMS etc.) — a stripped env can send
    # the child probing for TPUs on CPU-only hosts
    env = {**os.environ, "PYTHONPATH": os.path.join(repo, "src")}
    proc = subprocess.run(
        [sys.executable, "-c", snippet],
        capture_output=True, text=True, timeout=1200, env=env, cwd=repo,
    )
    assert "SHARDED_ENGINE_OK" in proc.stdout, proc.stderr[-2000:]


# ------------------------------------------------- fused and tiered paths
#
# The bucketed dense/kernel backends serve a store no wider than
# FUSED_MAX_WIDTH with one fused device program (prefilter, full-width
# gather, intersect); a wider store takes the host prefilter and the tier
# plan.  Every store here is narrow, so "tiered" lowers the limit below it.

PATHS = ("fused", "tiered", "host")


def _serve_path(co, q, path, monkeypatch, **kw):
    """Answers and the batch record of ``co``'s engine on one path."""
    from repro.serve import engine as engine_mod

    with monkeypatch.context() as mp:
        if path == "tiered":
            mp.setattr(engine_mod, "FUSED_MAX_WIDTH", 0)
        got = co.engine.query_batch(q, backend="host" if path == "host" else "dense", **kw)
    return got, co.engine.last_stats


def _trim_unused_rows(co):
    """Empty the label rows no query reads: L_out of the condensation's sinks
    and L_in of its sources (a sink reaches, and a source is reached by, no
    other vertex), so the mixes hold empty rows the length prefilter
    decides.  Verdicts stay exact."""
    dag = co.engine._fallback_graph
    sinks = np.flatnonzero(np.diff(dag.indptr) == 0)
    sources = np.flatnonzero(np.diff(dag.reverse().indptr) == 0)
    o = co.engine.oracle.with_updated_rows({int(v): [] for v in sinks},
                                           {int(v): [] for v in sources})
    co.engine.refresh(o, epoch=co.engine.epoch)


def _mix(g, rng, mix):
    """The random set (uniform pairs) or the equal set (half reachable),
    each with every u == v pair and two corner pairs."""
    from repro.graph.reach import sample_reachability_batch

    if mix == "random":
        q = rng.integers(0, g.n, size=(1500, 2)).astype(np.int32)
    else:
        q = sample_reachability_batch(g, 1500, rng)[0]
    diag = np.arange(g.n, dtype=np.int32)
    return np.concatenate([q, np.stack([diag, diag], 1),
                           np.array([[0, g.n - 1], [g.n - 1, 0]], np.int32)])


@pytest.mark.parametrize("mix", ["random", "equal"])
def test_fused_tiered_and_host_paths_agree_with_bfs(rng, monkeypatch, mix):
    kinds = {"same": 0, "empty": 0, "level": 0, "labels": 0}
    for name, g in _graph_families(rng):
        truth = _truth_matrix(g.n, *g.edges())
        co = build_oracle(g)
        _trim_unused_rows(co)
        q = _mix(g, rng, mix)
        exp = truth[q[:, 0], q[:, 1]]
        prefiltered = set()
        for path in PATHS:
            got, stats = _serve_path(co, q, path, monkeypatch)
            assert (got == exp).all(), (name, path, int((got != exp).sum()))
            assert stats["path"] == (None if path == "host" else path), (name, path)
            assert not any(stats["degraded"].values()), (name, path, stats["degraded"])
            prefiltered.add(stats["n_prefiltered"])
        assert len(prefiltered) == 1, (name, prefiltered)
        # what decided each pair, in condensation ids
        o, lv = co.engine.oracle, co.engine.level
        cu, cv = co.comp[q[:, 0]], co.comp[q[:, 1]]
        same = cu == cv
        empty = ~same & ((o.out_len[cu] == 0) | (o.in_len[cv] == 0))
        level = ~same & ~empty & (lv[cu] >= lv[cv])
        kinds["same"] += int(same.sum())
        kinds["empty"] += int(empty.sum())
        kinds["level"] += int(level.sum())
        kinds["labels"] += int((~same & ~empty & ~level).sum())
        assert prefiltered == {int((same | empty | level).sum())}, name
    assert all(kinds.values()), kinds


@pytest.mark.parametrize("rung", ["budget_quarantine", "deadline", "device_fault"])
def test_paths_agree_on_every_rung(rng, monkeypatch, rung):
    """Under a half-size budget view with a quarter of the rows
    quarantined, past the deadline, and with an injected device failure,
    the three paths give BFS's verdicts and the same prefiltered count."""
    import time
    import warnings

    from repro.ft import inject
    from repro.serve.budget import label_bytes, truncate_store

    for name, g in _graph_families(rng):
        truth = _truth_matrix(g.n, *g.edges())
        co = build_oracle(g)
        q = _mix(g, rng, "equal")
        exp = truth[q[:, 0], q[:, 1]]
        kw, fault = {}, None
        if rung == "budget_quarantine":
            co.engine.set_budget(truncate_store(co.oracle,
                                                budget_bytes=label_bytes(co.oracle) // 2))
            qmask = np.zeros(co.oracle.n, dtype=bool)
            qmask[rng.integers(0, co.oracle.n, size=max(co.oracle.n // 4, 1))] = True
            co.engine.set_quarantine(qmask, None)
        elif rung == "deadline":
            kw["deadline"] = time.monotonic() - 1.0
        else:
            fault = {"serve.device_dispatch": 0}
        prefiltered = set()
        for path in PATHS:
            with warnings.catch_warnings(), inject.active(inject.Injector(fault or {})):
                warnings.simplefilter("ignore")
                got, stats = _serve_path(co, q, path, monkeypatch, **kw)
            deg = stats["degraded"]
            assert (got == exp).all(), (name, path, int((got != exp).sum()))
            prefiltered.add(stats["n_prefiltered"])
            if rung == "budget_quarantine":
                assert deg["quarantined"] > 0, (name, path)
                # the tier programs run only for rows the host prefilter left
                undecided = q.shape[0] - deg["quarantined"] - stats["n_prefiltered"]
                want = {"fused": "fused", "tiered": "tiered" if undecided else None}
                assert stats["path"] == want.get(path), (name, path)
            elif path != "host":
                kind = "deadline_to_host" if rung == "deadline" else "device_to_host"
                assert stats["path"] is None and deg[kind] > 0, (name, path, deg)
        assert len(prefiltered) == 1, (name, rung, prefiltered)


def _device_batches(path):
    from repro.obs import metrics

    return metrics.snapshot()["engine_device_batches_total"]["values"].get(f"path={path}", 0)


def test_store_wider_than_a_lane_row_takes_the_tiered_path(rng):
    import dataclasses

    from repro.graph.csr import INVALID
    from repro.serve.engine import FUSED_MAX_WIDTH

    g = layered_dag(80, avg_out=2.5, seed=2)
    truth = _truth_matrix(g.n, *g.edges())
    o = distribution_labeling(g)
    q = rng.integers(0, g.n, size=(1000, 2)).astype(np.int32)

    def widen(mat, width):
        out = np.full((mat.shape[0], width), INVALID, dtype=np.int32)
        out[:, : mat.shape[1]] = mat
        return out

    for width, path in ((FUSED_MAX_WIDTH, "fused"), (FUSED_MAX_WIDTH + 8, "tiered")):
        wide = dataclasses.replace(o, L_out=widen(o.L_out, width), L_in=widen(o.L_in, width))
        eng = QueryEngine(wide, backend="dense", level=topo_levels(g))
        before = _device_batches(path)
        got = eng.query_batch(q)
        assert (got == truth[q[:, 0], q[:, 1]]).all(), width
        assert eng.last_stats["path"] == path, width
        assert _device_batches(path) == before + 1, width
        assert max(t["width"] for t in eng.last_stats["tiers"]) == width


@pytest.mark.parametrize("k", [1, 3, 255, 257])
def test_pad_rows_never_reach_the_answers(rng, k):
    """A pad row is (0, 0), which the fused program decides as reachable:
    a batch of unreachable pairs padded to its tile stays all False."""
    from repro.serve.planner import tile_rows

    g = random_dag(70, 200, seed=1)
    truth = _truth_matrix(g.n, *g.edges())
    eng = QueryEngine(distribution_labeling(g), backend="dense", level=topo_levels(g))
    neg = np.argwhere(~truth).astype(np.int32)
    q = neg[rng.choice(neg.shape[0], size=k)]
    got = eng.query_batch(q)
    assert got.shape == (k,) and not got.any()
    assert eng.last_stats["path"] == "fused"
    assert [(t["count"], t["rows"]) for t in eng.last_stats["tiers"]] == [
        (k, tile_rows(k, eng.min_tile))]

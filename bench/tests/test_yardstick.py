"""The benchmark's own generators, reference, samplers, loops and tables."""
from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from bench.yardstick import graphs, loops, mix, peaks, reach, samplers, work


@pytest.mark.parametrize("name,scale", [("agrocyc", 1.0), ("mtbrv", 1.0), ("xmark", 1.0),
                                        ("nasa", 1.0), ("cit-Patents", 0.01)])
@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_generators_byte_identical_to_the_program(name, scale, seed):
    from repro.graph.csr import from_edges
    from repro.graph.generators import PAPER_DATASETS, paper_dataset_analogue

    spec = PAPER_DATASETS[name]
    n, src, dst = graphs.table1_edges(spec["n"], spec["m"], spec["family"], seed, scale)
    ours = from_edges(n, src, dst)
    theirs = paper_dataset_analogue(name, scale, seed=seed)
    assert ours.indptr.tobytes() == theirs.indptr.tobytes()
    assert ours.indices.tobytes() == theirs.indices.tobytes()


@pytest.mark.parametrize("scale", [0.02, 1.0])
def test_citeseer_has_the_sources_edge_count(scale):
    """The program's size rule floors m at n / 2; the copy draws the
    source's m through the same family, byte for byte."""
    from repro.graph.csr import from_edges
    from repro.graph.generators import layered_dag

    n, src, dst = graphs.table1_edges(693947, 312282, "layered", 7, scale)
    m = int(312282 * scale)
    assert m * 0.999 <= np.unique(src * n + dst).size <= m
    theirs = layered_dag(n, avg_out=m / n, seed=7)
    assert from_edges(n, src, dst).indices.tobytes() == theirs.indices.tobytes()


def _graph(seed=3):
    return graphs.table1_edges(4000, 12000, "layered", seed)


def test_closure_matches_the_program_bfs():
    from repro.graph.csr import from_edges
    from repro.graph.reach import reachable_set

    n, src, dst = _graph()
    adj = reach.Adjacency(n, src, dst)
    g = from_edges(n, src, dst)
    sources = np.arange(0, n, 37)
    keys = reach.closure(adj, sources)
    for s in sources:
        mine = keys[(keys // n) == s] % n
        assert np.array_equal(mine, np.nonzero(reachable_set(g, int(s)))[0])
    u = np.repeat(sources, 5)
    v = np.random.default_rng(0).integers(0, n, u.size)
    want = np.array([bool(reachable_set(g, int(a))[b]) or a == b for a, b in zip(u, v)])
    assert np.array_equal(reach.reaches(keys, n, u, v), want)


def test_topo_levels_increase_along_edges():
    n, src, dst = _graph()
    level = reach.topo_levels(reach.Adjacency(n, src, dst))
    assert (level[src] < level[dst]).all()


def test_equal_pairs_are_half_reachable():
    n, src, dst = _graph()
    adj = reach.Adjacency(n, src, dst)
    p = samplers.equal_pairs(adj, 2000, np.random.default_rng(1))
    assert p.shape == (2000, 2)
    truth = reach.reaches(reach.closure(adj, p[:, 0]), n, p[:, 0], p[:, 1])
    assert truth.sum() == 1000 and not (p[:, 0] == p[:, 1]).any()
    again = samplers.equal_pairs(adj, 2000, np.random.default_rng(1))
    assert np.array_equal(p, again)


def test_poisson_offsets():
    t = loops.poisson_offsets(1000.0, 2.0, np.random.default_rng(0))
    assert (np.diff(t) >= 0).all() and t[0] >= 0 and t[-1] < 2.0
    assert t.size == 2000


def test_bursty_offsets_come_only_in_on_phases():
    # on 0.3 s, off 0.2 s over 1.2 s: on-time 0.3 + 0.3 + 0.2 = 0.8 s
    t = loops.poisson_offsets(1000.0, 1.2, np.random.default_rng(0), on_s=0.3, off_s=0.2)
    assert t.size == 800 and (np.diff(t) >= 0).all() and t[-1] < 1.2
    assert ((t % 0.5) < 0.3 + 1e-12).all()


def test_equal_pairs_take_any_reachable_share():
    n, src, dst = _graph()
    adj = reach.Adjacency(n, src, dst)
    p = samplers.equal_pairs(adj, 2000, np.random.default_rng(1), share=0.25)
    assert reach.reaches(reach.closure(adj, p[:, 0]), n, p[:, 0], p[:, 1]).sum() == 500


def test_popularity_in_turn_and_zipf():
    turn = mix.Popularity({}, 10, 2, seed=1)
    assert [turn.block(0, k) for k in range(3)] == [0, 1, 2]
    assert [turn.block(1, k) for k in range(3)] == [5, 6, 7]
    spec = {"zipf_s": 1.2, "hot_shift_requests": 500}
    hot = mix.Popularity(spec, 100, 1, seed=1)
    seq = np.array([hot.block(0, k) for k in range(1000)])
    first, second = np.bincount(seq[:500], minlength=100), np.bincount(seq[500:], minlength=100)
    assert first.max() > 500 * 0.15 and second.max() > 500 * 0.15   # a hot block
    assert first.argmax() != second.argmax()                        # that moved
    again = mix.Popularity(spec, 100, 1, seed=1)
    assert [again.block(0, k) for k in range(1000)] == seq.tolist()


def test_open_loop_times_from_the_due_instant():
    async def slow_submit(x):
        await asyncio.sleep(0.02)
        return x

    offsets = np.array([0.0, 0.0, 0.01, 0.05])
    payloads = [np.array([i]) for i in range(4)]
    log = asyncio.run(loops.open_loop(slow_submit, payloads, offsets))
    assert sorted(log.index) == [0, 1, 2, 3] and log.pending == 0
    assert len(log.late_s) == 4 and min(log.late_s) >= 0
    for i, start in zip(log.index, log.start):
        assert start == pytest.approx(log.t0 + offsets[i])
    lat = np.asarray(log.finish) - np.asarray(log.start)
    assert (lat >= 0.02).all()


def test_open_loop_gives_up_on_a_request_that_never_answers():
    async def never(x):
        await asyncio.sleep(3600)

    log = asyncio.run(loops.open_loop(never, [np.zeros(1)], np.zeros(1), grace_s=0.05))
    assert log.pending == 1 and not log.index


def test_closed_loop_waits_for_each_answer():
    inflight = []

    async def submit(x):
        inflight.append(1)
        assert len(inflight) <= 3
        await asyncio.sleep(0.005)
        inflight.pop()
        return x

    t0 = time.monotonic()
    log = asyncio.run(loops.closed_loop(submit, lambda c, k: (k, np.array([c])), 3, 0.1))
    assert time.monotonic() - t0 < 1.0 and log.pending == 0
    assert len(log.index) >= 3 * 10


def test_intersect_bytes_count_only_entries_ids_and_verdicts():
    out_len = np.array([1, 2, 0, 3])
    in_len = np.array([2, 0, 1, 1])
    u, v = np.array([0, 3]), np.array([2, 0])
    # 70,000 hops need 3 bytes, 300 vertices 2 bytes
    assert work.intersect_bytes(out_len, in_len, u, v, 70_000, 300) == (1 + 1 + 3 + 2) * 3 + 2 * 2 * 2 + 1
    assert work.id_bytes(256) == 1 and work.id_bytes(257) == 2


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_relabel_keeps_the_work_and_moves_the_ids():
    from repro.build.engine import build_distribution_labels
    from repro.graph.csr import from_edges

    n, src, dst = graphs.table1_edges(4500, 4800, "sparse", 7)
    runs = []
    for seed in (0, 1, 2**31 + 5):
        n2, s2, d2 = graphs.relabel(n, src, dst, seed)
        assert sorted(np.bincount(s2, minlength=n)) == sorted(np.bincount(np.unique(src * n + dst) // n, minlength=n))
        o = build_distribution_labels(from_edges(n2, s2, d2), impl="wave")
        runs.append((o.build_stats["n_waves"], o.total_label_size, s2[:20].tobytes()))
    assert len({r[:2] for r in runs}) == 1 and len({r[2] for r in runs}) == 3

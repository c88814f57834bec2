"""The construction engine: wave/bitset builder vs reference vs BFS truth.

Headline property: the wave engine produces BYTE-IDENTICAL finalized labels
to the seed scalar reference builder under the same vertex order, across the
same five graph families the serve engine is tested on — and both agree with
BFS ground truth.  Plus: wave-schedule soundness (members pairwise mutually
unreachable), bitset helper units, device-engine parity, and (slow) property
tests of completeness / non-redundancy for the wave engine.
"""
import numpy as np
import pytest

from repro.build import bitset
from repro.build.engine import build_distribution_labels
from repro.build.waves import dfs_intervals, wave_schedule
from repro.core.distribution import distribution_labeling
from repro.graph.csr import from_edges
from repro.graph.generators import layered_dag, random_dag, tree_dag
from repro.graph.reach import reachable_set, reaches_bit, transitive_closure_bits
from repro.graph.scc import condense_to_dag


def _dag_families(rng):
    """Five families mirroring tests/test_serve_engine.py, condensed to DAGs
    (the construction engine's input contract)."""
    fams = [
        ("random_dag", random_dag(70, 200, seed=1)),
        ("layered_dag", layered_dag(80, avg_out=2.5, seed=2)),
        ("tree_dag", tree_dag(90, branching=4, seed=3)),
    ]
    n = 60
    src, dst = rng.integers(0, n, 170), rng.integers(0, n, 170)
    fams.append(("cyclic", condense_to_dag(from_edges(n, src, dst))[0]))
    n = 80
    src, dst = rng.integers(0, n // 2, 60), rng.integers(0, n // 2, 60)
    fams.append(("isolated", condense_to_dag(from_edges(n, src, dst))[0]))
    return fams


def _assert_identical(ref, wav, tag):
    assert ref.L_out.tobytes() == wav.L_out.tobytes(), tag
    assert ref.L_in.tobytes() == wav.L_in.tobytes(), tag
    assert np.array_equal(ref.out_len, wav.out_len), tag
    assert np.array_equal(ref.in_len, wav.in_len), tag
    assert np.array_equal(ref.hop_rank, wav.hop_rank), tag


def test_wave_byte_identical_to_reference_all_families(rng):
    for name, g in _dag_families(rng):
        ref = build_distribution_labels(g, impl="reference")
        wav = build_distribution_labels(g, impl="wave")
        _assert_identical(ref, wav, name)


def test_wave_byte_identical_under_order_variants(rng):
    g = random_dag(120, 360, seed=8)
    for order_name in ("degree_product", "degree_sum", "random"):
        ref = build_distribution_labels(g, impl="reference", order_name=order_name)
        wav = build_distribution_labels(g, impl="wave", order_name=order_name)
        _assert_identical(ref, wav, order_name)


def test_wave_complete_vs_bfs_truth(rng):
    """Engine labels answer reachability exactly (Theorem 3), all families."""
    for name, g in _dag_families(rng):
        oracle = build_distribution_labels(g, impl="wave")
        tc = transitive_closure_bits(g)
        for u in range(g.n):
            for v in range(g.n):
                if u == v:
                    continue
                assert oracle.query(u, v) == reaches_bit(tc, u, v), (name, u, v)


def test_wave_handles_small_wave_caps(rng):
    """Forcing tiny waves (more batching boundaries) must not change labels."""
    g = layered_dag(150, avg_out=2.5, seed=4)
    ref = build_distribution_labels(g, impl="reference")
    for max_wave in (2, 7, 64):
        wav = build_distribution_labels(g, impl="wave", max_wave=max_wave)
        _assert_identical(ref, wav, f"max_wave={max_wave}")


def test_wave_schedule_members_mutually_unreachable(rng):
    """Soundness of the certificate: no wave member reaches another —
    both schedulers."""
    for name, g in _dag_families(rng):
        order = np.argsort(-g.out_degree().astype(np.int64), kind="stable").astype(np.int64)
        for scheduler in ("onepass", "blocked"):
            waves = wave_schedule(g, order, scheduler=scheduler)
            assert int(waves.sum()) == g.n, (name, scheduler)
            base = 0
            for wlen in waves:
                members = order[base : base + int(wlen)]
                for v in members:
                    reach = reachable_set(g, int(v))
                    others = members[members != v]
                    assert not reach[others].any(), (name, scheduler, int(v))
                base += int(wlen)


def test_onepass_schedule_equals_blocked_closure(rng):
    """Scheduler equivalence: with ``block >= n`` the per-block closure
    scheduler carves maximal greedy waves with exact conflicts — exactly
    what the one-pass windowed scheduler produces for ANY block size."""
    from repro.build.waves import wave_schedule_blocked
    from repro.core.order import get_order

    for name, g in _dag_families(rng):
        order = get_order(g, "degree_product")
        for max_wave in (2, 7, 64, 256):
            one = wave_schedule(g, order, max_wave=max_wave)
            blk = wave_schedule_blocked(
                g, order, max_wave=max_wave, block=max(g.n, max_wave)
            )
            assert np.array_equal(one, blk), (name, max_wave)


def test_onepass_schedule_budget_fallback_sound(rng):
    """A starved edge budget routes through bisection + conflict-with-all
    (or interval) fallbacks — the schedule must stay sound regardless."""
    from repro.core.order import get_order

    g = layered_dag(400, avg_out=2.0, seed=5)
    order = get_order(g, "degree_product")
    waves = wave_schedule(g, order, exact_budget=40)
    assert int(waves.sum()) == g.n
    base = 0
    for wlen in waves:
        members = order[base : base + int(wlen)]
        for v in members:
            reach = reachable_set(g, int(v))
            assert not reach[members[members != v]].any(), int(v)
        base += int(wlen)


def test_dfs_intervals_sound(rng):
    """u -> v implies post[v] in [low[u], post[u]] for every traversal."""
    g = random_dag(80, 240, seed=6)
    P, L = dfs_intervals(g, n_traversals=2)
    for u in range(g.n):
        reach = reachable_set(g, u)
        for v in np.nonzero(reach)[0]:
            for t in range(P.shape[0]):
                assert L[t, u] <= P[t, v] <= P[t, u], (u, int(v), t)


def test_device_gate_does_not_swallow_a_broken_jax(monkeypatch):
    """A jax that fails to start must surface, not quietly route an
    accelerator build to a host engine."""
    import jax

    from repro.build.engine import _device_backend_available

    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        _device_backend_available()


def test_auto_impl_routes_and_matches(rng):
    g = random_dag(300, 900, seed=9)
    auto = distribution_labeling(g)  # n < 4096 -> reference path
    assert getattr(auto, "build_impl") == "reference"
    wav = distribution_labeling(g, impl="wave")
    _assert_identical(auto, wav, "auto-vs-wave")


# ---------------------------------------------------------------------------
# bitset helper units
# ---------------------------------------------------------------------------


def test_bitset_group_or_and_gather(rng):
    keys = rng.integers(0, 10, 64).astype(np.int64)
    words = rng.integers(0, 2**63 - 1, (64, 2)).astype(np.uint64)
    uk, ow = bitset.group_or(keys, words)
    assert np.array_equal(uk, np.unique(keys))
    for i, k in enumerate(uk):
        expect = np.bitwise_or.reduce(words[keys == k], axis=0)
        assert np.array_equal(ow[i], expect)

    g = random_dag(40, 120, seed=3)
    indptr, indices = g.indptr.astype(np.int64), g.indices.astype(np.int64)
    verts = np.array([0, 5, 17], dtype=np.int64)
    nbrs, seg = bitset.csr_gather(indptr, indices, verts)
    expect = np.concatenate([g.out_neighbors(int(v)) for v in verts])
    assert np.array_equal(nbrs, expect)
    assert np.array_equal(seg, np.repeat([0, 1, 2], [len(g.out_neighbors(int(v))) for v in verts]))


def test_bitset_member_expansion(rng):
    w = 130  # spans 3 words
    mb = bitset.member_bits(w)
    assert mb.shape == (w, 3)
    rows, members, counts = bitset.expand_member_bits(mb, w)
    assert np.array_equal(rows, np.arange(w))
    assert np.array_equal(members, np.arange(w))
    assert np.array_equal(counts, np.ones(w, dtype=np.int64))
    # multi-bit rows expand row-major with ascending members
    combo = np.zeros((2, 3), dtype=np.uint64)
    combo[0] = mb[3] | mb[77] | mb[129]
    combo[1] = mb[0]
    rows, members, counts = bitset.expand_member_bits(combo, w)
    assert rows.tolist() == [0, 0, 0, 1]
    assert members.tolist() == [3, 77, 129, 0]
    assert counts.tolist() == [3, 1]
    assert bitset.popcount_u64(combo).tolist() == [3, 1]


def test_pack_bool_rows_u32(rng):
    mat = rng.random((7, 45)) < 0.3
    packed = bitset.pack_bool_rows_u32(mat)
    assert packed.shape == (7, 2)
    for i in range(7):
        for j in range(45):
            assert bool((packed[i, j // 32] >> np.uint32(j % 32)) & 1) == mat[i, j]


def test_ell_slabs_cover_all_edges(rng):
    """The degree-sorted slab decomposition lists every edge exactly once
    (row i of slab s = neighbor slots [s*w, (s+1)*w) of vertex perm[i])."""
    g = random_dag(90, 400, seed=13)
    indptr, indices = g.indptr.astype(np.int64), g.indices.astype(np.int64)
    perm, pos_of, slabs = bitset.ell_slabs(indptr, indices, g.n, width=4)
    assert np.array_equal(perm[pos_of], np.arange(g.n))
    per_vertex = {v: [] for v in range(g.n)}
    for slab in slabs:
        for i, row in enumerate(slab):
            per_vertex[int(perm[i])].extend(int(x) for x in row if x != -1)
    total = 0
    for v in range(g.n):
        assert per_vertex[v] == list(g.out_neighbors(v)), v
        total += len(per_vertex[v])
    assert total == g.m


# ---------------------------------------------------------------------------
# sparse device wave engine (ELL expansion, on-device append)
# ---------------------------------------------------------------------------


def test_device_engine_byte_identical_all_families(rng):
    """Fast rows: the XLA expansion path (same dataflow the Pallas kernel
    compiles on TPU) across the five serve-test graph families."""
    from repro.build.engine_jax import distribution_labeling_device

    for name, g in _dag_families(rng):
        ref = build_distribution_labels(g, impl="reference")
        dev = distribution_labeling_device(g, max_wave=32, expand="xla")
        _assert_identical(ref, dev, name)


def test_device_engine_byte_identical_under_order_variants(rng):
    from repro.build.engine_jax import distribution_labeling_device

    g = random_dag(120, 360, seed=8)
    for order_name in ("degree_product", "degree_sum", "random"):
        ref = build_distribution_labels(g, impl="reference", order_name=order_name)
        dev = distribution_labeling_device(
            g, order_name=order_name, max_wave=32, expand="xla"
        )
        _assert_identical(ref, dev, order_name)


def test_device_engine_label_matrix_growth(rng):
    """A deliberately tiny starting l_max forces the overflow-grow-rerun
    path; labels must stay byte-identical."""
    from repro.build.engine_jax import distribution_labeling_device

    g = random_dag(60, 170, seed=7)
    ref = build_distribution_labels(g, impl="reference")
    dev = distribution_labeling_device(g, max_wave=16, l_max=2, expand="xla")
    _assert_identical(ref, dev, "l_max growth")
    # an l_max below the reference's minimum row width that never overflows
    # must still finalize to the min-width-8 INVALID-padded layout
    from repro.graph.csr import from_edges as _fe

    g2 = _fe(3, [0, 1], [1, 2])
    ref2 = build_distribution_labels(g2, impl="reference")
    dev2 = distribution_labeling_device(g2, max_wave=4, l_max=4, expand="xla")
    assert dev2.L_out.shape == ref2.L_out.shape == (3, 8)
    _assert_identical(ref2, dev2, "min width pad")


@pytest.mark.parametrize("prune_cap", [2, 8, 64])
def test_device_engine_prune_cap_fallback(prune_cap):
    """Caps far below the cone sizes force the all-rows fallback of the
    compacted prune gather mid-build; labels must stay byte-identical."""
    from repro.build.engine_jax import distribution_labeling_device

    for name, g in _dag_families(np.random.default_rng(5)):
        ref = build_distribution_labels(g, impl="reference")
        dev = distribution_labeling_device(g, max_wave=16, expand="xla",
                                           prune_cap=prune_cap)
        _assert_identical(ref, dev, f"{name} prune_cap {prune_cap}")


def test_device_engine_pallas_interpret_row():
    """One fast interpret-mode row through the actual Pallas ELL kernel."""
    from repro.build.engine_jax import distribution_labeling_device

    g = random_dag(40, 110, seed=11)
    ref = build_distribution_labels(g, impl="reference")
    dev = distribution_labeling_device(
        g, max_wave=16, expand="pallas", interpret=True
    )
    _assert_identical(ref, dev, "pallas interpret")


def test_device_engine_sharded_expansion(rng):
    """The shard_map vertex-sharded expansion (single-device mesh on CPU;
    the same in/out specs place shards on real meshes)."""
    import jax
    from jax.sharding import Mesh

    from repro.build.engine_jax import distribution_labeling_device

    g = layered_dag(80, avg_out=2.5, seed=2)
    ref = build_distribution_labels(g, impl="reference")
    mesh = Mesh(np.array(jax.devices()), ("data",))
    dev = distribution_labeling_device(g, max_wave=16, expand="xla", mesh=mesh)
    _assert_identical(ref, dev, "shard_map mesh")


def test_engine_impl_device_routing_and_stats(rng):
    """impl='device' routes through the engine entry point; every build
    carries the scheduler-cost breakdown breadcrumb."""
    g = random_dag(70, 200, seed=1)
    ref = build_distribution_labels(g, impl="reference")
    dev = build_distribution_labels(g, impl="device", expand="xla")
    _assert_identical(ref, dev, "engine impl=device")
    for o, impl in ((ref, "reference"), (dev, "device")):
        stats = o.build_stats
        assert stats["impl"] == impl == o.build_impl
        assert {"schedule_seconds", "sweep_seconds", "n_waves"} <= set(stats)
    assert dev.build_stats["scheduler"] == "onepass"
    assert dev.build_stats["n_waves"] >= 1


@pytest.mark.slow
def test_device_engine_hardware_parity():
    """The hardware configuration: Pallas expansion (interpret off-TPU,
    compiled on TPU), wide waves spanning multiple uint32 words, and the
    engine-scheduled wave cap."""
    from repro.build.engine_jax import distribution_labeling_device

    g = layered_dag(300, avg_out=1.2, seed=9)
    host = build_distribution_labels(g, impl="wave")
    dev = distribution_labeling_device(g, max_wave=96, expand="pallas")
    _assert_identical(host, dev, "device-vs-host")


# The hypothesis property tests (Theorems 3-4 for the wave engine) live in
# tests/test_build_properties.py — module-level importorskip would skip this
# whole file on hypothesis-less environments.

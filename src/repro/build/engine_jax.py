"""Sparse device formulation of the wave-batched construction sweep.

The host engine (``engine.py``) and this module share one dataflow per wave
and per direction:

  1. prune:   pruned[u] = OR_{h in L(u)} hop_mask[h]      (gather + OR-reduce)
  2. reach:   masked multi-source BFS from the wave members where pruned
              member-bits do not expand                    (ELL OR-gather)
  3. append:  labeled = visited & ~pruned -> rank appends  (segment scatter)

Everything inside a wave runs ON DEVICE:

  * frontier expansion is the packed-frontier ELL kernel
    (``kernels/frontier_ell.py``) over the degree-sorted neighbor slabs of
    ``bitset.ell_slabs`` — operand footprint O(m + n*width), never the dense
    ``n x n/32`` adjacency bits the old demonstrator materialized
    (``expand="xla"`` swaps the Pallas call for an equivalent jnp gather —
    the fast path on CPU hosts, same dataflow),
  * the BFS fixpoint is a ``lax.while_loop`` — zero host round-trips per
    level (prune verdicts within a wave are static, see ``waves.py``),
  * the label append is a device segment scatter: member bits unpack to
    per-vertex column positions (``lens + prefix-popcount``) and one
    ``.at[rows, cols].set(ranks, mode="drop")`` lands every (vertex, rank)
    append of the wave into the dense label matrix.  Per-level results
    never round-trip to host; only a one-word overflow flag is read back
    per direction, and the label matrices come down ONCE at finalize.
  * with ``mesh=`` given, each slab's expansion runs under ``shard_map``
    with destination rows sharded over the mesh's data axes and the (tiny,
    packed) frontier words replicated — the vertex-sharded layout of
    ``core/distribution_jax.py``; waves stay sequential, the sweep inside a
    wave is embarrassingly data-parallel.

Labels are byte-identical to the host engine's — asserted in tests across
the serve-test graph families.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from repro.build import bitset
from repro.build.engine import _hop_rank, sort_label_rows
from repro.build.waves import wave_schedule
from repro.obs import trace
from repro.obs.state import ON
from repro.core.oracle import ReachabilityOracle, finalize_labels
from repro.core.order import get_order
from repro.graph.csr import CSRGraph, INVALID


def _expand_fn(slabs, pos_of, n, wm, expand_impl, interpret, block_n, mesh):
    """Build the per-level expansion closure: frontier words [n, wm] ->
    OR-gathered words [n, wm] (one BFS step for every member at once)."""
    import jax.numpy as jnp

    def _slab_xla(slab, f_pad):
        idx = jnp.where(slab == INVALID, n, slab)
        return jnp.bitwise_or.reduce(f_pad[idx], axis=1)

    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        axes = tuple(ax for ax in mesh.axis_names if ax != "model")
        shards = 1
        for ax in axes:
            shards *= mesh.shape[ax]

        def _sharded(slab, f_pad):
            pad = (-slab.shape[0]) % shards
            if pad:
                slab = jnp.pad(slab, ((0, pad), (0, 0)), constant_values=INVALID)
            out = shard_map(
                _slab_xla, mesh=mesh,
                in_specs=(P(axes, None), P(None, None)),
                out_specs=P(axes, None),
            )(slab, f_pad)
            return out[: out.shape[0] - pad] if pad else out

        slab_fn = _sharded
    elif expand_impl == "pallas":
        from repro.kernels.ops import frontier_or

        def slab_fn(slab, f_pad):
            return frontier_or(slab, f_pad[:-1], block_n=block_n, interpret=interpret)
    else:
        slab_fn = _slab_xla

    slab_arrs = [jnp.asarray(s) for s in slabs]
    pos = jnp.asarray(pos_of)

    def expand(f):  # uint32[n, wm] -> uint32[n, wm]
        f_pad = jnp.concatenate([f, jnp.zeros((1, wm), dtype=jnp.uint32)])
        out_perm = jnp.zeros((n, wm), dtype=jnp.uint32)
        for slab in slab_arrs:
            r = slab.shape[0]
            part = slab_fn(slab, f_pad)
            out_perm = out_perm.at[:r, :].set(out_perm[:r] | part)
        return out_perm[pos]

    return expand


def _make_wave_step(n, w, l_max, expand, prune_cap=None, donate=False):
    """One direction of Algorithm 2 for a whole wave, fully on device.

    ``prune_cap``: prune verdicts are computed lazily per level for the
    rows the BFS actually visited — a fixed-size ``prune_cap`` gather when
    the new frontier fits (cost tracks cone size, not n), falling back to
    the dense all-rows reduce on levels that visit more.  ``donate=True``
    donates the target label matrix and length vector into the jit so the
    append updates in place instead of device-to-device copying the whole
    matrix every wave; the step returns the pre-wave lengths so an
    overflowing sweep can be undone (appends only wrote columns past the
    old watermark) before growing and re-running.
    """
    import jax
    import jax.numpy as jnp

    wm = (w + 31) // 32
    word = np.arange(w, dtype=np.int32) // 32
    bit = np.uint32(1) << (np.arange(w, dtype=np.uint32) % np.uint32(32))
    if prune_cap is None:
        prune_cap = max(256, n // 8)
    prune_cap = min(prune_cap, n)

    def wave_step(L_src, L_tgt, len_tgt, members, valid, ranks):
        wordj = jnp.asarray(word)
        bitj = jnp.asarray(bit)

        # 1. hop_mask[h] = member words of members whose prune row holds h.
        #    Scatter-ADD is exact: each (member, hop) pair is unique, and
        #    distinct members in one word carry distinct bits, so add == OR.
        #    Row n stays zero (gather parking); row n+1 absorbs the scatter
        #    parking of padded member slots and INVALID label entries.
        rows_src = L_src[jnp.where(valid, members, 0)]  # [w, l_max]
        hops = jnp.where(valid[:, None] & (rows_src != INVALID), rows_src, n + 1)
        hop_mask = jnp.zeros((n + 2, wm), dtype=jnp.uint32)
        hop_mask = hop_mask.at[hops, wordj[:, None]].add(bitj[:, None])

        tgt_hops = jnp.where(L_tgt != INVALID, L_tgt, n)  # [n, l_max]

        # 2. fixpoint masked reach — a device while_loop, no host syncs.
        #    Verdicts are filled in lazily: each level computes them for the
        #    rows the previous level just visited (frontier-restricted
        #    gather), so the loop exits only after every visited row has its
        #    verdict — the final body makes no change, and a no-change body
        #    computed verdicts for all pending rows before expanding.
        start_rows = jnp.where(valid, members, n)  # n = out of bounds -> drop
        visited0 = jnp.zeros((n, wm), dtype=jnp.uint32).at[start_rows, wordj].add(
            bitj, mode="drop"
        )
        pruned0 = jnp.zeros((n, wm), dtype=jnp.uint32)
        computed0 = jnp.zeros(n, dtype=bool)

        def cond(state):
            return state[3]

        def body(state):
            v, pruned, computed, _ = state
            need = (v != 0).any(axis=1) & ~computed

            def sparse(p):
                # gather only the needy rows' label rows: OOB fill rows
                # clamp on gather and drop on scatter, so they are inert
                idx = jnp.nonzero(need, size=prune_cap, fill_value=n)[0]
                verd = jnp.bitwise_or.reduce(hop_mask[tgt_hops[idx]], axis=1)
                return p.at[idx].set(verd, mode="drop")

            def dense(p):
                verd = jnp.bitwise_or.reduce(hop_mask[tgt_hops], axis=1)
                return jnp.where(need[:, None], verd, p)

            pruned = jax.lax.cond(need.sum() <= prune_cap, sparse, dense, pruned)
            computed = computed | need
            new = v | expand(v & ~pruned)
            return new, pruned, computed, jnp.any(new != v)

        visited, pruned, _, _ = jax.lax.while_loop(
            cond, body, (visited0, pruned0, computed0, jnp.bool_(True))
        )

        # 3. segment-scatter append: member bits -> (row, lens + prefix) cols
        labeled = visited & ~pruned  # [n, wm] (never-visited rows are zero)
        bits_u = (labeled[:, word] >> jnp.asarray(np.arange(w) % 32, jnp.uint32)) & 1
        on = bits_u.astype(bool)  # [n, w]
        prefix = jnp.cumsum(bits_u, axis=1, dtype=jnp.int32) - bits_u.astype(jnp.int32)
        pos = len_tgt[:, None] + prefix
        cols = jnp.where(on, pos, l_max)  # l_max is out of bounds -> drop
        row_ids = jnp.arange(n, dtype=jnp.int32)[:, None]
        L_new = L_tgt.at[row_ids, cols].set(
            jnp.broadcast_to(ranks[None, :], (n, w)), mode="drop"
        )
        overflow = jnp.any(on & (pos >= l_max))
        len_new = len_tgt + bits_u.astype(jnp.int32).sum(axis=1)
        # len_tgt rides through as the pre-wave watermark: the overflow-undo
        # needs it, and under donation the caller no longer holds it
        return L_new, len_new, overflow, len_tgt

    if donate:
        return jax.jit(wave_step, donate_argnums=(1, 2))
    return jax.jit(wave_step)


def _make_undo():
    """Restore a donated label matrix to its pre-wave watermark: appends
    only ever write columns >= the old row length (which held INVALID), so
    masking those columns back to INVALID is an exact rollback."""
    import functools as _ft

    import jax
    import jax.numpy as jnp

    @_ft.partial(jax.jit, donate_argnums=(0,))
    def undo(L, len_prev):
        cols = jnp.arange(L.shape[1], dtype=jnp.int32)[None, :]
        return jnp.where(cols >= len_prev[:, None], INVALID, L)

    return undo


def certification_mask(labeled_rev, visited_rev, labeled_fwd, visited_fwd, members, w):
    """Device mirror of ``bitset.violation_mask`` — which members of an
    optimistic wave ran on stale prune sets.

    Inputs are the two sweeps' end-of-wave masks as the device engine
    already materializes them (uint32[n, ceil(w/32)]; ``labeled`` =
    ``visited & ~pruned``), plus the wave's member vertex ids.  Because the
    device engine keeps the sweep directions in separate arrays, member j
    is bit j in BOTH — no bank offsets — and the violation intersection is
    the same word math as the host pass: member j's reverse sweep is
    violated when some lower-ranked wave-mate i both appended into
    L_in(v_j) (``labeled_fwd[members][j]`` bit i) and labeled a row the
    reverse sweep visited (touch matrix of ``visited_rev``/``labeled_rev``);
    forward is symmetric.  Returns bool[w].  This is the schema the device
    engine will adopt speculative waves through — the wave-step outputs it
    needs (visited, pruned) already exist on device."""
    import jax.numpy as jnp

    wm = (w + 31) // 32
    word = np.arange(w, dtype=np.int32) // 32
    shift = np.arange(w, dtype=np.uint32) % np.uint32(32)
    # triangular prefix masks (bits < j), packed uint32[w, wm]
    jj = np.arange(w)
    pref_bool = jj[None, :] < jj[:, None]
    pref = jnp.asarray(bitset.pack_bool_rows_u32(pref_bool))

    def unpack(m):  # uint32[n, wm] -> bool[n, w]
        return ((m[:, word] >> jnp.asarray(shift)) & 1).astype(bool)

    def touch(v_mask, a_mask):  # T[j] = OR of a_mask rows with v-bit j set
        vb = unpack(v_mask)  # [n, w]
        return jnp.bitwise_or.reduce(
            jnp.where(vb[:, :, None], a_mask[:, None, :], jnp.uint32(0)), axis=0
        )  # [w, wm]

    own_rev = labeled_rev[members] & pref
    own_fwd = labeled_fwd[members] & pref
    t_rev = touch(visited_rev, labeled_rev)
    t_fwd = touch(visited_fwd, labeled_fwd)
    return ((own_fwd & t_rev) | (own_rev & t_fwd)).any(axis=1)


def _finalize_side(L, lens, n) -> np.ndarray:
    """Device label matrix -> the reference builder's byte layout (rows
    ascending, INVALID padded, width = next multiple of 8, min 8)."""
    lens = np.asarray(lens)
    lmax = int(lens.max()) if n else 1
    width = max(((max(lmax, 1) + 7) // 8) * 8, 8)
    mat = np.asarray(L[:, :width])
    if mat.shape[1] < width:  # small l_max that never overflowed: pad out
        pad = np.full((mat.shape[0], width - mat.shape[1]), INVALID, dtype=np.int32)
        mat = np.concatenate([mat, pad], axis=1)
    return sort_label_rows(mat)


def distribution_labeling_device(
    g: CSRGraph,
    order: Optional[np.ndarray] = None,
    order_name: str = "degree_product",
    max_wave: int = 64,
    l_max: int = 16,
    ell_width: int = 16,
    expand: str = "auto",
    interpret: bool | None = None,
    block_n: int = 128,
    mesh=None,
    waves: Optional[np.ndarray] = None,
    prune_cap: Optional[int] = None,
    donate: Optional[bool] = None,
) -> ReachabilityOracle:
    """Full sparse device wave build (host loop over waves, device sweeps).

    ``expand="pallas"`` drives the frontier through the Pallas ELL kernel
    (interpret mode off-TPU), ``"xla"`` through the equivalent jnp gather;
    ``"auto"`` picks pallas on TPU and xla elsewhere.  ``l_max`` is the
    starting label-matrix width — overflowing waves grow it geometrically
    and re-run after a watermark undo (appends only wrote columns past the
    pre-wave row lengths, so masking those back to INVALID is exact).
    ``prune_cap`` bounds the per-level frontier-restricted prune gather
    (default max(256, n // 8)); ``donate`` donates the target label matrix
    + lengths into the wave-step jit so appends update in place instead of
    device-to-device copying the whole matrix every wave (default: on for
    accelerator backends, off on CPU where XLA ignores donation).
    """
    import jax
    import jax.numpy as jnp

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if expand == "auto":
        expand = "pallas" if jax.default_backend() == "tpu" else "xla"
    if donate is None:
        donate = jax.default_backend() != "cpu"
    n = g.n
    if n == 0:
        return finalize_labels([], [], hop_rank=np.empty(0, dtype=np.int32))
    if order is None:
        order = get_order(g, order_name)
    order = np.asarray(order, dtype=np.int64)
    if waves is None:
        waves = wave_schedule(g, order, max_wave=max_wave)
    # the static member width follows the ACTUAL schedule (a caller may hand
    # in waves cut at a different cap), rounded to whole uint32 words
    max_wave = int(max(int(np.max(waves)) if waves.size else 1, 1))
    max_wave = ((max_wave + 31) // 32) * 32 if max_wave > 32 else max_wave
    g_rev = g.reverse()

    # reverse pass expands u -> in-neighbors w (edge w -> u): destination-
    # stationary rows = packed OUT-neighbor slabs; forward pass symmetric
    # with the reverse graph's rows
    slabs_out = bitset.ell_slabs(
        g.indptr.astype(np.int64), g.indices.astype(np.int64), n, width=ell_width
    )
    slabs_in = bitset.ell_slabs(
        g_rev.indptr.astype(np.int64), g_rev.indices.astype(np.int64), n, width=ell_width
    )

    w = int(max_wave)
    wm = (w + 31) // 32
    kw = dict(expand_impl=expand, interpret=interpret, block_n=block_n, mesh=mesh)
    # expansion closures are l_max-independent: built once (slab upload +
    # trace happen here only); the wave steps rebuild on overflow growth
    ex_out = _expand_fn(slabs_out[2], slabs_out[1], n, wm, **kw)
    ex_in = _expand_fn(slabs_in[2], slabs_in[1], n, wm, **kw)
    step_rev = None  # built lazily per l_max (re-built on overflow growth)
    step_fwd = None
    undo = _make_undo()  # shape-polymorphic: retraces per l_max as needed

    L_out = jnp.full((n, l_max), INVALID, dtype=jnp.int32)
    L_in = jnp.full((n, l_max), INVALID, dtype=jnp.int32)
    out_len = jnp.zeros(n, dtype=jnp.int32)
    in_len = jnp.zeros(n, dtype=jnp.int32)
    ranks_of = np.arange(n, dtype=np.int32)

    base = 0
    for wi, wlen in enumerate(waves):
        wlen = int(wlen)
        # annotate=True also emits a jax.profiler TraceAnnotation when the
        # tracer's jax_annotations flag is on, so device profiles line up
        # with the exported Chrome timeline wave-for-wave
        sp = (trace.span("build.wave", cat="build",
                         args={"index": wi, "size": wlen}, annotate=True)
              if ON.enabled else trace.NOOP_SPAN)
        with sp:
            members = np.full(w, 0, dtype=np.int32)
            members[:wlen] = order[base : base + wlen]
            valid = np.zeros(w, dtype=bool)
            valid[:wlen] = True
            ranks = np.zeros(w, dtype=np.int32)
            ranks[:wlen] = ranks_of[base : base + wlen]
            m_j, v_j, r_j = jnp.asarray(members), jnp.asarray(valid), jnp.asarray(ranks)
            # reverse then forward: the forward prune set L_out(v_j) must see
            # the member's own rank, which the reverse sweep just appended
            for direction in ("rev", "fwd"):
                while True:
                    if step_rev is None:
                        step_rev = _make_wave_step(
                            n, w, l_max, ex_out, prune_cap=prune_cap, donate=donate)
                        step_fwd = _make_wave_step(
                            n, w, l_max, ex_in, prune_cap=prune_cap, donate=donate)
                    # the target matrix + lengths may be donated into the
                    # step, so rebind to the outputs unconditionally — the
                    # old buffers are dead either way, and res[3] carries
                    # the pre-wave lengths an overflow undo needs
                    if direction == "rev":
                        res = step_rev(L_in, L_out, out_len, m_j, v_j, r_j)
                        L_out, out_len = res[0], res[1]
                    else:
                        res = step_fwd(L_out, L_in, in_len, m_j, v_j, r_j)
                        L_in, in_len = res[0], res[1]
                    if not bool(res[2]):  # overflow flag: one scalar per sweep
                        break
                    # overflow: watermark-undo the partial appends (they only
                    # wrote columns past the pre-wave lengths), grow the label
                    # matrices, and re-run this sweep
                    if ON.enabled:
                        sp.event("overflow_regrow", l_max=l_max * 2)
                    if direction == "rev":
                        L_out, out_len = undo(L_out, res[3]), res[3]
                    else:
                        L_in, in_len = undo(L_in, res[3]), res[3]
                    l_max *= 2
                    grow = functools.partial(
                        jnp.pad, pad_width=((0, 0), (0, l_max // 2)),
                        constant_values=INVALID,
                    )
                    L_out, L_in = grow(L_out), grow(L_in)
                    step_rev = step_fwd = None
        base += wlen

    return ReachabilityOracle(
        L_out=_finalize_side(L_out, out_len, n),
        L_in=_finalize_side(L_in, in_len, n),
        out_len=np.asarray(out_len),
        in_len=np.asarray(in_len),
        hop_rank=_hop_rank(order, n),
    )


# backwards-compatible alias (the dense demonstrator's public name)
distribution_labeling_wave_jax = distribution_labeling_device

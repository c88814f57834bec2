"""Pallas TPU kernel: packed-frontier OR-gather over ELL neighbor slabs.

out[i, :] = OR_{s : nbr[i, s] != INVALID}  F[nbr[i, s], :]      (uint32 words)

One BFS level of the sparse device wave engine (``build/engine_jax.py``):
``F`` is the packed member-frontier word matrix (bit j of word k = "wave
member 32k+j's BFS currently expands here"), ``nbr`` one destination-
stationary ELL slab.  TPUs have no scatter atomics, so the schedule is
inverted — each grid step owns a (TN)-row destination tile and PULLS the
frontier rows its neighbors name.

``F`` stays in HBM (``memory_space=pl.ANY``) whatever its row count: the
tile's neighbor ids arrive twice, as a flat SMEM block (scalars that
address one row DMA per valid slot, all in flight on one semaphore) and as
a VMEM block (the vector mask of valid slots).  INVALID slots may sit
anywhere in a row: they issue no DMA, and the mask drops their stale
scratch rows.  Gathered rows (WM words padded to 128 lanes, the HBM row
tiling) land slot-major in a (d, TN, 128) VMEM scratch and OR-reduce over
the d slots into the output tile.  VMEM use is O(d * TN), independent of ``n``.

Unlike ``bitset_mm.py`` (whose A operand is a dense packed n x n/32 bit
matrix — closure-sized memory), the slab rows are int32 neighbor IDS: the
operand footprint is O(edges), which is what lets the wave engine run at
graph scale without materializing adjacency bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INVALID = -1


def _frontier_or_kernel(nbr_s, nbr_v, f_hbm, o_ref, gbuf, sem, *, block_n, max_deg):
    def each_valid_slot(action):
        def row_body(i, carry):
            def slot_body(s, carry):
                idx = nbr_s[i * max_deg + s]

                @pl.when(idx != INVALID)
                def _():
                    action(pltpu.make_async_copy(
                        f_hbm.at[pl.ds(idx, 1)], gbuf.at[s, pl.ds(i, 1)], sem.at[0]))

                return carry

            return jax.lax.fori_loop(0, max_deg, slot_body, carry)

        jax.lax.fori_loop(0, block_n, row_body, 0)

    each_valid_slot(lambda c: c.start())
    each_valid_slot(lambda c: c.wait())

    nbr = nbr_v[...]  # int32[TN, d]
    acc = jnp.zeros(o_ref.shape, jnp.uint32)
    for s in range(max_deg):
        # INVALID slots hold stale rows from an earlier tile: mask them
        acc = acc | jnp.where(nbr[:, s:s + 1] != INVALID, gbuf[s], jnp.uint32(0))
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def frontier_or_pallas(
    nbr: jnp.ndarray,  # int32[r, d]  ELL slab, INVALID-padded
    f: jnp.ndarray,    # uint32[n_src, WM]  packed frontier words
    block_n: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    r, d = nbr.shape
    n_src, wm = f.shape
    assert r % block_n == 0, (r, block_n)
    # HBM tiles rows to 128 lanes: DMA whole lane-padded rows
    lanes = -(-wm // 128) * 128
    f = jnp.pad(f, ((0, 0), (0, lanes - wm)))
    grid = (r // block_n,)
    kernel = functools.partial(_frontier_or_kernel, block_n=block_n, max_deg=d)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n * d,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # F stays in HBM
        ],
        out_specs=pl.BlockSpec((block_n, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, lanes), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((d, block_n, lanes), jnp.uint32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(nbr.reshape(-1), nbr, f)[:, :wm]

"""Pallas TPU kernel: batched hop-label intersection (the oracle query core).

For a query batch, decide per query whether the INVALID-padded label rows
a[i, :] and b[i, :] share a value. TPU-native design: instead of the CPU
sorted-merge (branchy, serial), each query does an La x Lb all-pairs compare
on the VPU, fully parallel across the query tile.

Layout: the wrapper hands the kernel the TRANSPOSED rows — aT int32[La, B],
bT int32[Lb, B] — so queries run along the 128-wide lane axis and label
slots along sublanes. One a-slot is then a (1, TB) sublane row read with a
dynamic sublane slice, compared against the whole (TLb, TB) b block by a
sublane broadcast, and reduced over sublanes. The verdict comes out
lane-dense as int32[1, B].

VMEM stays bounded at any label width: the grid is (query tiles, a-slot
blocks, b-slot blocks), each step holds one (TLa, TB) and one (TLb, TB)
block plus a (TLb, TB) compare temporary, and the (1, TB) output block
stays resident across the two reduction axes, OR-accumulating hits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INVALID = -1

# label slots per block on each side; wider rows split across grid steps
SLOT_BLOCK = 512


def _intersect_kernel(a_ref, b_ref, o_ref, *, block_la):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    b = b_ref[...]  # int32[TLb, TB]

    def body(j, hit):
        row = a_ref[pl.ds(j, 1), :]  # int32[1, TB]: slot j of every query
        eq = (b == row) & (row != INVALID)
        return hit | jnp.max(eq.astype(jnp.int32), axis=0, keepdims=True)

    hit = jax.lax.fori_loop(0, block_la, body, jnp.zeros(o_ref.shape, jnp.int32))
    o_ref[...] = o_ref[...] | hit


def slot_block(width: int) -> int:
    """Slots per grid step for a label side of ``width`` columns."""
    return width if width <= SLOT_BLOCK else SLOT_BLOCK


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def label_intersect_pallas(
    a_t: jnp.ndarray,
    b_t: jnp.ndarray,
    block_b: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    """a_t: int32[La, B], b_t: int32[Lb, B] -> int32[1, B] (1 = shared hop).

    B must be a multiple of block_b, and La / Lb multiples of their
    ``slot_block`` (ops.py pads)."""
    La, B = a_t.shape
    Lb, _ = b_t.shape
    ta, tb = slot_block(La), slot_block(Lb)
    assert B % block_b == 0 and La % ta == 0 and Lb % tb == 0, (La, Lb, B, block_b)
    grid = (B // block_b, La // ta, Lb // tb)
    return pl.pallas_call(
        functools.partial(_intersect_kernel, block_la=ta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ta, block_b), lambda i, j, k: (j, i)),
            pl.BlockSpec((tb, block_b), lambda i, j, k: (k, i)),
        ],
        out_specs=pl.BlockSpec((1, block_b), lambda i, j, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(a_t, b_t)

"""Pallas TPU kernel: padded-neighbor-list (ELL) SpMM for GNN message passing.

out[i, :] = sum_j  w[i, j] * X[nbr[i, j], :]       nbr INVALID-padded

TPU adaptation note (DESIGN.md §2): GPU GNN kernels scatter per-edge with
atomics; TPUs have no atomics, so we invert the schedule — destination-
stationary tiles. Each grid step owns a (TN)-node tile; its padded neighbor
ids are small int32 VMEM blocks, and source rows are pulled from the
feature matrix (kept whole in ANY/HBM space) with dynamic row slices, one
neighbor slot at a time, accumulating in a VMEM f32 tile. The dynamic row
gather is the honest hot spot — on hardware each dynamic row read is a strided HBM
read issued by the scalar core (Mosaic supports dynamic sublane slices);
interpret mode validates the semantics.

The (beyond-paper) degree-sorted variant in ops.py reorders nodes by degree
so tiles have uniform slot counts, cutting wasted INVALID-slot bandwidth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INVALID = -1


def _ell_spmm_kernel(nbr_ref, wgt_ref, x_ref, o_ref, *, block_n, max_deg):
    nbr = nbr_ref[...]  # int32[TN, d]
    wgt = wgt_ref[...]  # f32[TN, d]
    acc = jnp.zeros_like(o_ref)

    def slot_body(s, acc):
        def row_body(i, acc):
            idx = nbr[i, s]
            safe = jnp.where(idx == INVALID, 0, idx)
            row = x_ref[pl.ds(safe, 1), :]  # [1, F]
            w = jnp.where(idx == INVALID, 0.0, wgt[i, s])
            return acc.at[i].add(w * row[0])

        return jax.lax.fori_loop(0, block_n, row_body, acc)

    acc = jax.lax.fori_loop(0, max_deg, slot_body, acc)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ell_spmm_pallas(
    nbr: jnp.ndarray,   # int32[n, d]
    wgt: jnp.ndarray,   # f32[n, d]
    x: jnp.ndarray,     # f32[n_src, F]
    block_n: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    n, d = nbr.shape
    n_src, F = x.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    kernel = functools.partial(_ell_spmm_kernel, block_n=block_n, max_deg=d)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec(x.shape, lambda i: (0, 0)),  # whole X visible (ANY/HBM)
        ],
        out_specs=pl.BlockSpec((block_n, F), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, F), x.dtype),
        interpret=interpret,
    )(nbr, wgt, x)

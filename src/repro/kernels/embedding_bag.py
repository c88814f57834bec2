"""Pallas TPU kernel: embedding-bag (sum) for the recsys sparse hot path.

out[b, :] = sum_s  mask[b, s] * T[idx[b, s], :]

JAX has no native EmbeddingBag; this is the fused gather+segment-sum. The
schedule mirrors ell_spmm (destination-stationary bag tiles, dynamic row
pulls from the table kept in ANY/HBM); on hardware the table rows stream
through VMEM once per referencing bag — the xDeepFM tables (10^6 rows x 10)
never fit VMEM, so per-row dynamic slices are the only TPU-shaped access.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INVALID = -1


def _embag_kernel(idx_ref, table_ref, o_ref, *, block_b, bag):
    idx = idx_ref[...]  # int32[TB, bag]
    acc = jnp.zeros_like(o_ref)

    def slot_body(s, acc):
        def row_body(b, acc):
            i = idx[b, s]
            safe = jnp.where(i < 0, 0, i)
            row = table_ref[pl.ds(safe, 1), :]
            valid = (i >= 0).astype(row.dtype)
            return acc.at[b].add(valid * row[0])

        return jax.lax.fori_loop(0, block_b, row_body, acc)

    acc = jax.lax.fori_loop(0, bag, slot_body, acc)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def embedding_bag_pallas(
    table: jnp.ndarray,  # f32[V, D]
    idx: jnp.ndarray,    # int32[B, bag], negative = padding
    block_b: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    B, bag = idx.shape
    V, D = table.shape
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)
    kernel = functools.partial(_embag_kernel, block_b=block_b, bag=bag)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, bag), lambda i: (i, 0)),
            pl.BlockSpec(table.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), table.dtype),
        interpret=interpret,
    )(idx, table)

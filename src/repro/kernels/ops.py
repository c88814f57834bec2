"""Public jit'd wrappers for the Pallas kernels.

Each wrapper pads inputs to tile multiples, dispatches to the kernel, and
slices the result back. ``interpret`` defaults to True off-TPU (this
container is CPU-only; TPU is the compile target).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.bitset_mm import bitset_mm_pallas
from repro.kernels.ell_spmm import ell_spmm_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.frontier_ell import frontier_or_pallas
from repro.kernels.label_intersect import label_intersect_pallas, slot_block

INVALID = -1


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int, fill) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def label_intersect(a, b, block_b: int = 256, interpret: bool | None = None):
    """int32[B, La] x int32[B, Lb] -> bool[B]. ``block_b`` is a multiple of
    128 (queries run along the lane axis)."""
    if interpret is None:
        interpret = not _on_tpu()
    B = a.shape[0]
    # the kernel wants slots on sublanes, queries on lanes
    at = _pad_axis(_pad_axis(a, 0, block_b, INVALID).T, 0,
                   slot_block(a.shape[1]), INVALID)
    bt = _pad_axis(_pad_axis(b, 0, block_b, INVALID).T, 0,
                   slot_block(b.shape[1]), INVALID)
    out = label_intersect_pallas(at, bt, block_b=block_b, interpret=interpret)
    return out[0, :B] != 0


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "block_w", "interpret"))
def bitset_mm(a_bits, x_bits, block_n=256, block_k=256, block_w=128, interpret=None):
    """uint32[n, ceil(k/32)] x uint32[k, wm] -> uint32[n, wm]."""
    if interpret is None:
        interpret = not _on_tpu()
    n, wk = a_bits.shape
    k, wm = x_bits.shape
    bn = min(block_n, max(8, n))
    bk = min(block_k, max(32, ((k + 31) // 32) * 32))
    bw = min(block_w, max(1, wm))
    ap = _pad_axis(_pad_axis(a_bits, 0, bn, 0), 1, bk // 32, 0)
    xp = _pad_axis(_pad_axis(x_bits, 0, bk, 0), 1, bw, 0)
    out = bitset_mm_pallas(ap, xp, block_n=bn, block_k=bk, block_w=bw, interpret=interpret)
    return out[:n, :wm]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret", "scale"),
)
def flash_attention(
    q,  # [B, Hq, S, D]
    k,  # [B, Hkv, T, D]
    v,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """GQA flash attention. Returns [B, Hq, S, D]."""
    if interpret is None:
        interpret = not _on_tpu()
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    if scale is None:
        scale = float(1.0 / np.sqrt(D))
    bq = min(block_q, S) if S % min(block_q, S) == 0 else S
    bk = min(block_k, T)
    # GQA: repeat kv heads (XLA fuses the broadcast into the gather; the
    # hillclimbed variant uses an index-map instead — see dryrun variants)
    kr = jnp.repeat(k, rep, axis=1).reshape(B * Hq, T, D)
    vr = jnp.repeat(v, rep, axis=1).reshape(B * Hq, T, D)
    qr = q.reshape(B * Hq, S, D)
    qp = _pad_axis(qr, 1, bq, 0)
    out = flash_attention_pallas(
        qp, kr, vr, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, interpret=interpret,
    )
    return out[:, :S].reshape(B, Hq, S, D)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def frontier_or(nbr, f, block_n: int = 128, interpret: bool | None = None):
    """Packed-frontier ELL OR-gather: int32[r, d], uint32[n_src, WM] ->
    uint32[r, WM] (one BFS level of the sparse device wave engine).  Each
    row's valid slots come first, INVALID padding after them — the layout
    ``bitset.ell_slabs`` builds."""
    if interpret is None:
        interpret = not _on_tpu()
    r = nbr.shape[0]
    if r == 0:
        return jnp.zeros((0, f.shape[1]), dtype=jnp.uint32)
    # row tiles stay a multiple of 8 sublanes, and the tile's flattened ids
    # (an SMEM block) a multiple of the 1024-word tile XLA lays 1-D int32
    # arrays out in — unless one tile holds the whole slab
    step = 1024 // math.gcd(nbr.shape[1], 1024)
    bn = min(-(-max(block_n, step) // step) * step, -(-r // 8) * 8)
    nbrp = _pad_axis(nbr, 0, bn, INVALID)
    out = frontier_or_pallas(nbrp, f, block_n=bn, interpret=interpret)
    return out[:r]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ell_spmm(nbr, wgt, x, block_n: int = 128, interpret: bool | None = None):
    """ELL SpMM: int32[n, d], f32[n, d], f32[n_src, F] -> f32[n, F]."""
    if interpret is None:
        interpret = not _on_tpu()
    n = nbr.shape[0]
    bn = min(block_n, n) if n % min(block_n, n) == 0 else n
    nbrp = _pad_axis(nbr, 0, bn, INVALID)
    wgtp = _pad_axis(wgt, 0, bn, 0.0)
    out = ell_spmm_pallas(nbrp, wgtp, x, block_n=bn, interpret=interpret)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def embedding_bag(table, idx, block_b: int = 128, interpret: bool | None = None):
    """f32[V, D] gathered/sum-reduced by int32[B, bag] (neg = pad) -> f32[B, D]."""
    if interpret is None:
        interpret = not _on_tpu()
    B = idx.shape[0]
    bb = min(block_b, B) if B % min(block_b, B) == 0 else B
    idxp = _pad_axis(idx, 0, bb, INVALID)
    out = embedding_bag_pallas(table, idxp, block_b=bb, interpret=interpret)
    return out[:B]


# re-export refs for tests/benches
ref = _ref

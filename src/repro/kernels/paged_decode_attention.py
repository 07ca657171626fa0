"""Pallas TPU paged flash-decode: one query token against a PAGED KV cache.

Same online-softmax flash-decode as :mod:`repro.kernels.decode_attention`,
but K/V live in a shared page pool ``(P, KV, d, page_size)`` instead of one
contiguous ``(B, KV, S, d)`` cache, and each batch row reads its pages
through a block table ``(B, nb)`` of page ids. The gather is free: the
block table is a scalar-prefetch operand (SMEM), so the BlockSpec index map
resolves ``block_tables[b, j]`` BEFORE the grid step's DMA is issued — the
kernel streams exactly the pages the row owns, one page per sequence tile,
and never materializes a contiguous copy of the cache (the jnp lowering in
``models.attention.paged_decode_attention_jnp`` does gather; that is the
CPU fallback, not the TPU path).

Grid: (B, KV, nb) with the page axis innermost. Unallocated block-table
entries hold a valid sentinel page id (0 — see serving/block_allocator.py),
so every index-map resolution is in bounds; their stale contents sit beyond
the row's valid ``length`` and are masked by the online softmax exactly
like the contiguous kernel's padding. Rotary embedding of q is fused at
position ``lengths - 1`` when ``rope_theta`` is given (cached keys are
rotated at write time).

The page size doubles as the sequence tile (``s_block == page_size``):
pages are not contiguous in the pool, so a tile cannot span pages. The
autotuner's ``paged_decode_attention`` entry therefore tunes the PAGE SIZE
itself — per-grid-step issue overhead pushes pages up, internal
fragmentation (half a page wasted per sequence on average) pushes them
down — and the engine consults it when constructing the pool.

Layout: q (B, H, d); k/v pools (L, P, KV, d, page_size) — the MODEL
layout of the whole stack, consumed directly so no caller ever slices or
relayouts the (large) pool on the decode hot path: the layer is one more
scalar-prefetch operand and the index map picks its pages, so a model's
layer scan carries the pool and updates it in place. A one-layer slab
(P, KV, d, page_size) is read as a pool of one layer. Head-major pages keep
the streamed tile's last two dims at ``(d, page_size)``, the whole trailing
extent of the pool, which is what the TPU lowering accepts for a one-head
slab. A page is held transposed, ``d`` before the tokens: with the tokens
(a multiple of 128 at the sizes served) in the lanes, the row-major pool is
unpadded, and it is also the layout the TPU picks for the array by default,
so no program relayouts it (a ``(page_size, 80)`` tile would pad 80 lanes to
128, and the default layout would hold the pool the other way round).
block_tables (B, nb) int32; lengths (B,) int32; layer () int32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import _rope_rotate

NEG_INF = -1e30


def _kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
            num_blocks: int, rope_theta: float | None):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(j * page_size < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                  # (G, d)
        if rope_theta is not None:
            q = _rope_rotate(q, length - 1, rope_theta)
        q = q * scale
        k = k_ref[0, 0].astype(jnp.float32)                  # (d, page)
        s = jnp.dot(q, k, preferred_element_type=jnp.float32)
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # (d, page)
        pv = jax.lax.dot_general(p, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(j == num_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rope_theta", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           layer=0, *, rope_theta: float | None = None,
                           interpret: bool = False):
    """q: (B, H, d); k/v pools: (L, P, KV, d, page) — the model layout, read
    in place (no slab slice or relayout on the hot path) at layer
    ``layer``; a 4-D (P, KV, d, page) slab is a pool of one layer (layer
    0); block_tables: (B, nb) int32 page ids; lengths: (B,) -> (B, H, d).

    ``rope_theta``: fuse rotary embedding of q at position ``lengths - 1``.
    """
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    b, h, d = q.shape
    kv, page = k_pages.shape[2], k_pages.shape[4]
    g = h // kv
    nb = block_tables.shape[1]
    scale = 1.0 / math.sqrt(d)

    qg = q.reshape(b, kv, g, d)
    kernel = functools.partial(_kernel, scale=scale, page_size=page,
                               num_blocks=nb, rope_theta=rope_theta)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # block_tables, lengths, layer
        grid=(b, kv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b_, k_, j, bt, ln, ly: (b_, k_, 0, 0)),
            # the paged gather: the tile for grid step (b, k, j) is the
            # row's j-th page of the layer, resolved from the prefetched
            # block table; the (d, page) slab of head k_ is contiguous in
            # the pool, so only owned pages ever move
            pl.BlockSpec((None, 1, 1, d, page),
                         lambda b_, k_, j, bt, ln, ly:
                         (ly[0], bt[b_, j], k_, 0, 0)),
            pl.BlockSpec((None, 1, 1, d, page),
                         lambda b_, k_, j, bt, ln, ly:
                         (ly[0], bt[b_, j], k_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, k_, j, bt, ln, ly: (b_, k_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), qg, k_pages, v_pages)
    return out.reshape(b, h, d)

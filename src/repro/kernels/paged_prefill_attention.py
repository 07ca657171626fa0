"""Pallas TPU paged prefill-chunk flash attention: a chunk vs a PAGED cache.

Same chunk-vs-cache online softmax as :mod:`repro.kernels.prefill_attention`
with K/V living in the shared page pool ``(P, KV, d, page_size)`` instead of
a contiguous per-slot cache — the paged counterpart, exactly as
:mod:`repro.kernels.paged_decode_attention` is to
:mod:`repro.kernels.decode_attention`. The block table is a scalar-prefetch
operand, so the BlockSpec index map resolves ``block_tables[b, j]`` before
each grid step's DMA and the kernel streams only the pages the row owns; the
sequence tile IS the page (tiles cannot span non-contiguous pages).

Unallocated table entries hold the sentinel page id 0; their stale contents
sit beyond the row's causal horizon ``start_len + r//G`` and are masked by
the online softmax. Rotary embedding of row r's query is fused at absolute
position ``start_len + r//G`` (cached keys are rotated at write time).

Layout: q (B, H, C, d) head-major; k/v pools (L, P, KV, d, page_size) —
the MODEL layout of the whole stack (pages held transposed, as in
:mod:`repro.kernels.paged_decode_attention`), read in place at a layer given
as one more scalar-prefetch operand (a (P, KV, d, page_size) slab is a pool
of one layer); block_tables (B, nb) int32; start_len (B,); layer () int32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.prefill_attention import _rope_rotate_rows

NEG_INF = -1e30


def _kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
            num_blocks: int, c: int, g: int, rope_theta: float | None):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = len_ref[b]

    @pl.when(j * page_size < start + c)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                  # (C*G, d)
        rows = jax.lax.broadcasted_iota(jnp.int32, (c * g, 1), 0)
        qpos = start + rows // g                             # (C*G, 1)
        if rope_theta is not None:
            q = _rope_rotate_rows(q, qpos, rope_theta)
        q = q * scale
        k = k_ref[0, 0].astype(jnp.float32)                  # (d, page)
        s = jnp.dot(q, k, preferred_element_type=jnp.float32)
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos <= qpos, s, NEG_INF)               # per-row horizon
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
def paged_prefill_attention(q, k_pages, v_pages, block_tables, start_len,
                            layer=0, *, rope_theta: float | None = None,
                            interpret: bool = False):
    """q: (B, H, C, d); k/v pools: (L, P, KV, d, page) read in place at
    layer ``layer``, the chunk's keys/values already written into the
    rows' pages; a 4-D (P, KV, d, page) slab is a pool of one layer (layer
    0); block_tables: (B, nb) int32 page ids; start_len: (B,) ->
    (B, H, C, d).

    ``rope_theta``: fuse rotary embedding of chunk query j at absolute
    position ``start_len + j``.
    """
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    b, h, c, d = q.shape
    kv, page = k_pages.shape[2], k_pages.shape[4]
    g = h // kv
    nb = block_tables.shape[1]
    scale = 1.0 / math.sqrt(d)

    qr = (q.reshape(b, kv, g, c, d).transpose(0, 1, 3, 2, 4)
          .reshape(b, kv, c * g, d))
    kernel = functools.partial(_kernel, scale=scale, page_size=page,
                               num_blocks=nb, c=c, g=g,
                               rope_theta=rope_theta)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # block_tables, start_len, layer
        grid=(b, kv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, c * g, d),
                         lambda b_, k_, j, bt, ln, ly: (b_, k_, 0, 0)),
            # the paged gather: grid step (b, k, j) streams the row's j-th
            # page of the layer, resolved from the prefetched block table
            pl.BlockSpec((None, 1, 1, d, page),
                         lambda b_, k_, j, bt, ln, ly:
                         (ly[0], bt[b_, j], k_, 0, 0)),
            pl.BlockSpec((None, 1, 1, d, page),
                         lambda b_, k_, j, bt, ln, ly:
                         (ly[0], bt[b_, j], k_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c * g, d),
                               lambda b_, k_, j, bt, ln, ly: (b_, k_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((c * g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, c * g, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(start_len, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), qr, k_pages, v_pages)
    return (out.reshape(b, kv, c, g, d).transpose(0, 1, 3, 2, 4)
            .reshape(b, h, c, d))

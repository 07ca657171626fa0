"""Pallas TPU paged latent-attention decode: one absorbed query token per
row, 16 heads (or any number) against one shared latent head in a PAGED
pool.

The paged counterpart of :func:`repro.models.mla.attend` for decode. A
latent row is ``W`` wide (the RMS-normed KV latent, ``r`` columns, then the
shared rotary key); every query head attends it whole, and the values are
its first ``r`` columns, so each page is read once for both products and
for all heads at once. The block table is a scalar-prefetch operand: the
index map resolves ``block_tables[b, j]`` before each grid step's DMA and
only the row's own pages move. Past a row's last page the index map
repeats that page, so no further page is fetched, and the step computes
nothing.

Grid: (B, nb), the page axis innermost, with an online softmax over the
pages. Layout: q (B, H, W) float32, absorbed and rotated; the pool
(L, P, 1, W, page) bf16, the model layout of the whole stack (pages held
transposed, tokens in the lanes, as the dense pools are), read in place at
``layer``, a scalar-prefetch operand; block_tables (B, nb) int32; lengths
(B,) int32 keys per row, the new token included. Returns (B, H, r) float32:
each head's weighted latent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, len_ref, layer_ref, q_ref, c_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
            num_blocks: int, rank: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(j * page_size < length)
    def _step():
        q = q_ref[0] * scale                                 # (H, W)
        c = c_ref[...].astype(jnp.float32)                   # (W, page)
        s = jnp.dot(q, c, preferred_element_type=jnp.float32)
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, c[:rank], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(j == num_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def paged_mla_decode_attention(q, pool, block_tables, lengths, layer=0, *,
                               scale: float, rank: int,
                               interpret: bool = False):
    """q: (B, H, W) absorbed queries; pool: (L, P, 1, W, page) read at
    ``layer`` (a 4-D (P, 1, W, page) slab is a pool of one layer);
    block_tables: (B, nb); lengths: (B,) -> (B, H, rank) float32."""
    if pool.ndim == 4:
        pool, layer = pool[None], 0
    b, h, w = q.shape
    page = pool.shape[-1]
    nb = block_tables.shape[1]
    kernel = functools.partial(_kernel, scale=scale, page_size=page,
                               num_blocks=nb, rank=rank)

    def page_of(b_, j, bt, ln, ly):
        # the row's j-th page, held at its last page once past its length
        last = jnp.maximum(ln[b_] - 1, 0) // page
        return (ly[0], bt[b_, jnp.minimum(j, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # block_tables, lengths, layer
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda b_, j, bt, ln, ly: (b_, 0, 0)),
            pl.BlockSpec((None, None, None, w, page), page_of),
        ],
        out_specs=pl.BlockSpec((1, h, rank),
                               lambda b_, j, bt, ln, ly: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q.astype(jnp.float32), pool)

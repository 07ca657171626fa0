"""Pallas TPU paged latent-attention prefill: a chunk of absorbed queries
against one shared latent head in a PAGED pool, causally.

The prefill counterpart of :mod:`repro.kernels.paged_mla_decode_attention`,
as :mod:`repro.kernels.paged_prefill_attention` is to the dense decode
kernel. The chunk's latent rows are already written into the rows' pages.
Query rows are (token, head) pairs, token-major, so row ``i`` of a row's
chunk sits at position ``start + i // H`` and sees the cache up to there.
The rows are cut into tiles of at most ``ROW_TILE``; a tile streams the
row's pages up to its last row's horizon, and past it the index map repeats
that page, so no further page moves and the step computes nothing.

Grid: (B, row tiles, nb), pages innermost, online softmax over the pages.
Layout: q (B, C*H, W) float32, absorbed and rotated, token-major; the pool
(L, P, 1, W, page) read in place at ``layer``; block_tables (B, nb);
start_len (B,) tokens already cached before the chunk. Returns
(B, C*H, r) float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: query rows a grid step holds: 32 tokens of 16 heads
ROW_TILE = 512


def _kernel(bt_ref, len_ref, layer_ref, q_ref, c_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
            num_blocks: int, rank: int, heads: int, tile: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = len_ref[b]
    horizon = start + ((i + 1) * tile - 1) // heads     # the tile's last row

    @pl.when(j * page_size <= horizon)
    def _step():
        q = q_ref[0] * scale                                 # (tile, W)
        rows = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        qpos = start + rows // heads                         # (tile, 1)
        c = c_ref[...].astype(jnp.float32)                   # (W, page)
        s = jnp.dot(q, c, preferred_element_type=jnp.float32)
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos <= qpos, s, NEG_INF)
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


@functools.partial(jax.jit, static_argnames=("heads", "scale", "rank",
                                             "interpret"))
def paged_mla_prefill_attention(q, pool, block_tables, start_len, layer=0,
                                *, heads: int, scale: float, rank: int,
                                interpret: bool = False):
    """q: (B, C*H, W) token-major absorbed queries of ``heads`` heads;
    pool: (L, P, 1, W, page) read at ``layer`` (a 4-D slab is a pool of
    one layer); block_tables: (B, nb); start_len: (B,) ->
    (B, C*H, rank) float32."""
    if pool.ndim == 4:
        pool, layer = pool[None], 0
    b, n, w = q.shape
    page = pool.shape[-1]
    nb = block_tables.shape[1]
    tile = min(n, ROW_TILE)
    padded = -(-n // tile) * tile
    q = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, padded - n), (0, 0)))
    kernel = functools.partial(_kernel, scale=scale, page_size=page,
                               num_blocks=nb, rank=rank, heads=heads,
                               tile=tile)

    def page_of(b_, i, j, bt, ln, ly):
        # the row's j-th page, held at the tile's horizon page past it
        last = jnp.minimum((ln[b_] + ((i + 1) * tile - 1) // heads) // page,
                           nb - 1)
        return (ly[0], bt[b_, jnp.minimum(j, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # block_tables, start_len, layer
        grid=(b, padded // tile, nb),
        in_specs=[
            pl.BlockSpec((1, tile, w),
                         lambda b_, i, j, bt, ln, ly: (b_, i, 0)),
            pl.BlockSpec((None, None, None, w, page), page_of),
        ],
        out_specs=pl.BlockSpec((1, tile, rank),
                               lambda b_, i, j, bt, ln, ly: (b_, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((tile, 1), jnp.float32),
            pltpu.VMEM((tile, 1), jnp.float32),
            pltpu.VMEM((tile, rank), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, padded, rank), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(start_len, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), q, pool)
    return out[:, :n]

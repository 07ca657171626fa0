"""Backend dispatch for the Pallas kernels.

``REPRO_KERNEL_BACKEND`` ∈ {auto, jnp, pallas, interpret}:
  auto       — pallas on TPU, jnp elsewhere (this container: jnp)
  jnp        — pure-jnp lowering (the pjit/dry-run path)
  pallas     — pl.pallas_call compiled for the device
  interpret  — pl.pallas_call(interpret=True): kernel body executed in python
               on CPU; used by the correctness test suite.

Model-facing layouts are (B, S, H, d); kernels are head-major — wrappers
transpose at the boundary (a no-op inside a jit once XLA picks layouts).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _pallas_decode
from repro.kernels.flash_attention import flash_attention as _pallas_flash
from repro.kernels.paged_decode_attention import \
    paged_decode_attention as _pallas_paged_decode
from repro.kernels.paged_mla_decode_attention import \
    paged_mla_decode_attention as _pallas_paged_mla_decode
from repro.kernels.paged_mla_prefill_attention import \
    paged_mla_prefill_attention as _pallas_paged_mla_prefill
from repro.kernels.paged_prefill_attention import \
    paged_prefill_attention as _pallas_paged_prefill
from repro.kernels.prefill_attention import \
    prefill_attention as _pallas_prefill_chunk
from repro.kernels.rmsnorm import rmsnorm as _pallas_rmsnorm
from repro.kernels.ssd_scan import ssd_chunk_scan as _pallas_ssd

_BACKEND = [None]  # lazily resolved; settable for tests


def set_backend(name: str | None):
    _BACKEND[0] = name


def backend() -> str:
    b = _BACKEND[0] or os.environ.get("REPRO_KERNEL_BACKEND", "auto")
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return b


def attention_prefill(q, k, v, *, causal: bool = True):
    """q: (B, S, H, d); k/v: (B, S, KV, d) -> (B, S, H, d)."""
    be = backend()
    if be == "jnp":
        from repro.models.attention import flash_attention_jnp
        return flash_attention_jnp(q, k, v, causal=causal)
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    o = _pallas_flash(qT, kT, vT, causal=causal, interpret=(be == "interpret"))
    return o.transpose(0, 2, 1, 3)


def attention_decode(q, k_cache, v_cache, lengths, rope_theta=None):
    """q: (B, 1, H, d); caches: (B, S, KV, d); lengths (B,) -> (B, 1, H, d).

    ``rope_theta``: fuse the query rotation (at position ``lengths - 1``)
    into the attention — no separate RoPE launch on the decode path."""
    be = backend()
    if be == "jnp":
        from repro.models.attention import decode_attention_jnp
        return decode_attention_jnp(q, k_cache, v_cache, lengths,
                                    rope_theta=rope_theta)
    kT = k_cache.transpose(0, 2, 1, 3)
    vT = v_cache.transpose(0, 2, 1, 3)
    o = _pallas_decode(q[:, 0], kT, vT, jnp.asarray(lengths, jnp.int32),
                       rope_theta=rope_theta,
                       interpret=(be == "interpret"))
    return o[:, None]


def attention_decode_paged(q, k_pages, v_pages, block_tables, lengths,
                           rope_theta=None, layer=0):
    """q: (B, 1, H, d); pools: (L, P, KV, d, page) read at ``layer``, or one
    layer's (P, KV, d, page) slab; block_tables: (B, nb); lengths (B,) ->
    (B, 1, H, d).

    Paged counterpart of :func:`attention_decode`: K/V are gathered through
    the per-row block table instead of read from a contiguous per-slot
    cache. Same fused-RoPE contract."""
    be = backend()
    if be == "jnp":
        from repro.models.attention import (layer_pages,
                                            paged_decode_attention_jnp)
        return paged_decode_attention_jnp(
            q, layer_pages(k_pages, layer), layer_pages(v_pages, layer),
            block_tables, lengths, rope_theta=rope_theta)
    # the paged kernel consumes the model-layout pool directly — slicing or
    # relayouting the pool per decode token would dwarf the attention itself
    o = _pallas_paged_decode(q[:, 0], k_pages, v_pages,
                             jnp.asarray(block_tables, jnp.int32),
                             jnp.asarray(lengths, jnp.int32), layer,
                             rope_theta=rope_theta,
                             interpret=(be == "interpret"))
    return o[:, None]


def attention_prefill_chunk(q, k_cache, v_cache, start_len, rope_theta=None):
    """q: (B, C, H, d) UN-rotated; caches: (B, S, KV, d) with the chunk's
    keys/values already scattered at ``start_len .. start_len+C-1``;
    start_len: (B,) -> (B, C, H, d).

    Chunk-vs-cache causal attention for chunked prefill. ``rope_theta``:
    fuse the per-token query rotation (chunk token j at absolute position
    ``start_len + j``) into the attention — no separate RoPE launch, and
    multi-slot batched prefill rows each get their own positions."""
    be = backend()
    if be == "jnp":
        from repro.models.attention import prefill_chunk_attention_jnp
        positions = jnp.asarray(start_len)[:, None] + \
            jnp.arange(q.shape[1])[None, :]
        return prefill_chunk_attention_jnp(q, k_cache, v_cache, positions,
                                           rope_theta=rope_theta)
    qT = q.transpose(0, 2, 1, 3)
    kT = k_cache.transpose(0, 2, 1, 3)
    vT = v_cache.transpose(0, 2, 1, 3)
    o = _pallas_prefill_chunk(qT, kT, vT, jnp.asarray(start_len, jnp.int32),
                              rope_theta=rope_theta,
                              interpret=(be == "interpret"))
    return o.transpose(0, 2, 1, 3)


def attention_prefill_chunk_paged(q, k_pages, v_pages, block_tables,
                                  start_len, rope_theta=None, layer=0):
    """q: (B, C, H, d) UN-rotated; pools: (L, P, KV, d, page) read at
    ``layer``, or one layer's (P, KV, d, page) slab; block_tables: (B, nb);
    start_len: (B,) -> (B, C, H, d).

    Paged counterpart of :func:`attention_prefill_chunk`: K/V are gathered
    through the per-row block table (Pallas scalar-prefetch gather on TPU,
    materialized gather on jnp). Same fused-RoPE contract."""
    be = backend()
    if be == "jnp":
        from repro.models.attention import (gather_pages, layer_pages,
                                            prefill_chunk_attention_jnp)
        k = gather_pages(layer_pages(k_pages, layer), block_tables)
        v = gather_pages(layer_pages(v_pages, layer), block_tables)
        positions = jnp.asarray(start_len)[:, None] + \
            jnp.arange(q.shape[1])[None, :]
        return prefill_chunk_attention_jnp(q, k, v, positions,
                                           rope_theta=rope_theta)
    # the paged kernel consumes the model-layout pool directly — slicing or
    # relayouting the pool per prefill chunk would dwarf the attention itself
    o = _pallas_paged_prefill(q.transpose(0, 2, 1, 3), k_pages, v_pages,
                              jnp.asarray(block_tables, jnp.int32),
                              jnp.asarray(start_len, jnp.int32), layer,
                              rope_theta=rope_theta,
                              interpret=(be == "interpret"))
    return o.transpose(0, 2, 1, 3)


def _latent_rows(pool, layer, block_tables):
    from repro.models.attention import gather_pages, layer_pages
    return gather_pages(layer_pages(pool, layer), block_tables)[:, :, 0]


def attention_mla_decode_paged(q, pool, block_tables, lengths, *,
                               scale: float, rank: int, layer=0):
    """q: (B, 1, H, W) absorbed, rotated queries; pool: (L, P, 1, W, page)
    latent pages read at ``layer``, or one layer's slab; block_tables:
    (B, nb); lengths (B,) keys per row -> (B, 1, H, rank) float32, each
    head's weighted latent."""
    be = backend()
    if be == "jnp":
        from repro.models.mla import attend
        rows = _latent_rows(pool, layer, block_tables)
        return attend(q, rows, jnp.asarray(lengths)[:, None] - 1, scale,
                      rank)
    o = _pallas_paged_mla_decode(q[:, 0], pool,
                                 jnp.asarray(block_tables, jnp.int32),
                                 jnp.asarray(lengths, jnp.int32), layer,
                                 scale=scale, rank=rank,
                                 interpret=(be == "interpret"))
    return o[:, None]


def attention_mla_prefill_paged(q, pool, block_tables, start_len, *,
                                scale: float, rank: int, layer=0):
    """q: (B, C, H, W) absorbed, rotated queries of a chunk whose latent
    rows are already in the pool at ``start_len ..``; pool as in
    :func:`attention_mla_decode_paged` -> (B, C, H, rank) float32."""
    be = backend()
    b, c, h, w = q.shape
    if be == "jnp":
        from repro.models.mla import attend
        rows = _latent_rows(pool, layer, block_tables)
        qpos = jnp.asarray(start_len)[:, None] + jnp.arange(c)[None, :]
        return attend(q, rows, qpos, scale, rank)
    o = _pallas_paged_mla_prefill(q.reshape(b, c * h, w), pool,
                                  jnp.asarray(block_tables, jnp.int32),
                                  jnp.asarray(start_len, jnp.int32), layer,
                                  heads=h, scale=scale, rank=rank,
                                  interpret=(be == "interpret"))
    return o.reshape(b, c, h, rank)


def ssd_intra_chunk(x, dt, cum, b_, c_):
    """x: (M, Q, H, P); dt/cum: (M, Q, H); b_/c_: (M, Q, N)."""
    be = backend()
    if be == "jnp":
        y, st = jax.vmap(ref.ssd_chunk_ref)(x, dt, cum, b_, c_)
        return y, st
    return _pallas_ssd(x, dt, cum, b_, c_, interpret=(be == "interpret"))


def fused_rmsnorm(x, w, eps: float = 1e-5):
    """x: (..., D); w: (D,)."""
    be = backend()
    if be == "jnp":
        return ref.rmsnorm_ref(x, w, eps)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    o = _pallas_rmsnorm(x2, w, eps=eps, interpret=(be == "interpret"))
    return o.reshape(shape)

"""Attention for the pjit path.

Two implementations:

- ``naive_attention`` — O(S^2) materialized, used for tiny smoke shapes and as
  the semantic oracle (mirrors kernels/ref.py).
- ``flash_attention_jnp`` — block-causal online-softmax attention built from
  ``lax.scan`` over KV blocks with a python loop over Q blocks, so causal
  attention only touches the lower-triangular blocks (≈2x HLO-FLOP saving vs
  a masked full product) and never materializes an (S, S) tensor. This is the
  lowering used by the production dry-run; the Pallas kernel in
  ``repro.kernels.flash_attention`` is the TPU runtime counterpart with the
  same blocking scheme.

All functions take q: (B, Sq, H, d) and k/v: (B, Skv, KV, d) with GQA
(H = G * KV) and return (B, Sq, H, d).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = -1e30


def _split_gqa(q: Array, num_kv: int) -> Array:
    b, s, h, d = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, d)


def naive_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    q_offset: int = 0) -> Array:
    """Reference attention. ``q_offset``: absolute position of q[:, 0]."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    qg = _split_gqa(q, kv).astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = kpos[None, :] <= qpos[:, None]
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def _flash_one_qblock(qg: Array, kb: Array, vb: Array, *, diag_mask: bool,
                      q_block: int, kv_block: int) -> Array:
    """qg: (B, qb, KV, G, d); kb/vb: (nj, B, kvb, KV, d) stacked KV blocks.

    Online-softmax scan over the nj KV blocks; only the final (diagonal)
    block receives the triangular mask when ``diag_mask``.
    """
    b, qb, kv, g, d = qg.shape
    nj = kb.shape[0]
    scale = 1.0 / math.sqrt(d)
    qg32 = qg.astype(jnp.float32) * scale

    tri = jnp.tril(jnp.ones((q_block, kv_block), dtype=bool))

    from repro.distributed import hints as _hints
    logits_bf16 = _hints.get("attn_logits_bf16")

    def body(carry, inputs):
        m, l, acc = carry
        kj, vj, is_diag = inputs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg32, kj.astype(jnp.float32))
        if diag_mask:
            s = jnp.where(jnp.logical_or(~is_diag, tri[None, None, None]), s, NEG_INF)
        if logits_bf16:  # halve the materialized block bytes; keep f32 stats
            s = s.astype(jnp.bfloat16)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1).astype(jnp.float32))
        p = jnp.exp(s.astype(jnp.float32) - m_new[..., None])
        if logits_bf16:
            p = p.astype(jnp.bfloat16)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, dtype=jnp.float32)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv, g, qb), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, qb), jnp.float32)
    a0 = jnp.zeros((b, kv, g, qb, d), jnp.float32)
    is_diag = jnp.arange(nj) == nj - 1
    body = jax.checkpoint(body)  # recompute block logits in backward
    from repro.models import layers as _layers
    (m, l, acc), _ = _layers.scan(body, (m0, l0, a0), (kb, vb, is_diag))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(qg.dtype)  # (B,qb,KV,G,d)


def flash_attention_jnp(q: Array, k: Array, v: Array, *, causal: bool = True,
                        q_block: int = 0, kv_block: int = 0) -> Array:
    """Block-causal flash attention (see module docstring)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    kv = k.shape[2]
    # adaptive blocks: at most 8 q-blocks so the unrolled cost-extrapolation
    # modules stay compilable; XLA tiles the inner products further anyway.
    q_block = q_block or max(1024, sq // 8)
    kv_block = kv_block or (q_block if causal else max(1024, skv // 8))
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    if sq % q_block or skv % kv_block or (causal and q_block != kv_block):
        return naive_attention(q, k, v, causal=causal)
    nq = sq // q_block

    qg = _split_gqa(q, kv)
    outs = []
    for i in range(nq):
        qi = qg[:, i * q_block:(i + 1) * q_block]
        hi = (i + 1) * kv_block if causal else skv
        nj = hi // kv_block
        kb = k[:, :hi].reshape(b, nj, kv_block, kv, d).swapaxes(0, 1)
        vb = v[:, :hi].reshape(b, nj, kv_block, kv, d).swapaxes(0, 1)
        outs.append(_flash_one_qblock(qi, kb, vb, diag_mask=causal,
                                      q_block=q_block, kv_block=kv_block))
    out = jnp.concatenate(outs, axis=1)  # (B, S, KV, G, d)
    return out.reshape(b, sq, h, d)


def prefill_chunk_attention_jnp(q: Array, k_full: Array, v_full: Array,
                                positions: Array,
                                rope_theta: float | None = None) -> Array:
    """Chunk-vs-cache causal attention (jnp lowering): C chunk tokens
    against the full cache (history + the chunk itself, already written).

    q: (B, C, H, d) UN-rotated; k_full/v_full: (B, S, KV, d); positions:
    (B, C) absolute position per chunk token. Materializes the
    (B, KV, G, C, S) logits tensor — the CPU/test path; the Pallas kernel
    in ``repro.kernels.prefill_attention`` is the TPU runtime counterpart
    streaming the cache with an online softmax.

    ``rope_theta``: rotate chunk query j at ``positions[:, j]`` in here
    (fused-RoPE prefill contract; cached keys are rotated at write time).
    Returns float32 (B, C, H, d) — callers cast.
    """
    b, c, h, d = q.shape
    s = k_full.shape[1]
    kvh = k_full.shape[2]
    g = h // kvh
    if rope_theta is not None:
        from repro.models import layers
        q = layers.apply_rope(q, positions, rope_theta)
    qg = q.reshape(b, c, kvh, g, d).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    logits = jnp.einsum("bckgd,bskd->bkgcs", qg,
                        k_full.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None, None, :] <= positions[:, :, None]  # (B,C,S)
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    pr = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgcs,bskd->bckgd", pr, v_full.astype(jnp.float32))
    return o.reshape(b, c, h, d)


def layer_pages(pool: Array, layer) -> Array:
    """One layer's (P, KV, d, page) slab of a whole stack's (L, P, KV, d,
    page) pool; a 4-D pool is one layer's slab already."""
    return pool if pool.ndim == 4 else pool[layer]


def gather_pages(pool: Array, block_tables: Array) -> Array:
    """Contiguous per-row view of a paged pool (the jnp lowering's
    materialized gather). pool: (P, KV, d, page) head-major model layout,
    pages held transposed; block_tables: (B, nb) int32 page ids ->
    (B, nb*page, KV, d)."""
    g = pool[block_tables]                     # (B, nb, KV, d, page)
    b, nb, kv, d, page = g.shape
    return g.transpose(0, 1, 4, 2, 3).reshape(b, nb * page, kv, d)


def paged_decode_attention_jnp(q: Array, k_pages: Array, v_pages: Array,
                               block_tables: Array, length: Array,
                               rope_theta: float | None = None) -> Array:
    """Single-token decode attention against a PAGED cache (jnp lowering).

    q: (B, 1, H, d); pools: (P, KV, d, page) model layout; block_tables:
    (B, nb) int32 page ids; length: (B,) valid prefix per row.

    The jnp fallback materializes the gathered view (:func:`gather_pages`)
    and defers to :func:`decode_attention_jnp` — correct everywhere, and
    cheap at CPU test shapes. The Pallas kernel
    (``repro.kernels.paged_decode_attention``) is the TPU runtime path that
    streams pages through the block table without the materialized copy.
    Sentinel (unallocated) table entries point at a real page whose stale
    contents lie beyond ``length`` — masked like cache padding.
    """
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    return decode_attention_jnp(q, k, v, length, rope_theta=rope_theta)


def decode_attention_jnp(q: Array, k_cache: Array, v_cache: Array,
                         length: Array,
                         rope_theta: float | None = None) -> Array:
    """Single-token decode attention against a (possibly seq-sharded) cache.

    q: (B, 1, H, d); caches: (B, S, KV, d); length: () or (B,) valid prefix.
    Softmax reductions run over the full S axis, so when S is sharded
    (long-context SP) XLA lowers max/sum to all-reduces — flash-decode
    combine for free.

    ``rope_theta``: rotate q at position ``length - 1`` in here (fused-RoPE
    decode contract; cached keys are already rotated at write time), so the
    caller issues no separate RoPE op on the decode hot path.
    """
    b, _, h, d = q.shape
    kv = k_cache.shape[2]
    s = k_cache.shape[1]
    if rope_theta is not None:
        from repro.models import layers
        pos = jnp.reshape(jnp.asarray(length), (-1,))[:, None] - 1  # (B|1, 1)
        q = layers.apply_rope(q, pos, rope_theta)
    qg = _split_gqa(q, kv)[:, 0].astype(jnp.float32)  # (B, KV, G, d)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32)) * scale
    length = jnp.asarray(length)
    valid = jnp.arange(s)[None, :] < jnp.reshape(length, (-1, 1))  # (B|1, S)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    norm = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / norm, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)

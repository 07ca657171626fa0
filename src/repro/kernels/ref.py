"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Layouts here match the KERNEL-facing layouts (head-major), not the model's
(B, S, H, d) — ops.py adapts.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Array = jax.Array
NEG_INF = -1e30


def flash_attention_ref(q: Array, k: Array, v: Array, *, causal: bool = True) -> Array:
    """q: (B, H, Sq, d); k/v: (B, KV, Skv, d). GQA H = G*KV. -> (B, H, Sq, d)."""
    b, h, sq, d = q.shape
    kv = k.shape[1]
    g = h // kv
    qg = q.reshape(b, kv, g, sq, d).astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qg, k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, k.shape[2]), bool))
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return o.reshape(b, h, sq, d).astype(q.dtype)


def rope_ref(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding oracle. x: (..., d); positions broadcastable to
    x.shape[:-1]. Mirrors models.layers.apply_rope's split-halves layout."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def decode_attention_ref(q: Array, k: Array, v: Array, lengths: Array,
                         rope_theta: float | None = None) -> Array:
    """q: (B, H, d); k/v: (B, KV, S, d); lengths: (B,). -> (B, H, d).

    ``rope_theta``: rotate q at position ``lengths - 1`` before attending
    (the fused-RoPE decode contract — cached k is already rotated)."""
    b, h, d = q.shape
    kv, s = k.shape[1], k.shape[2]
    g = h // kv
    if rope_theta is not None:
        q = rope_ref(q, (lengths - 1)[:, None], rope_theta).astype(q.dtype)
    qg = q.reshape(b, kv, g, d).astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, k.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)


def paged_decode_attention_ref(q: Array, k_pages: Array, v_pages: Array,
                               block_tables: Array, lengths: Array,
                               rope_theta: float | None = None) -> Array:
    """Paged flash-decode oracle: gather pages, defer to the dense oracle.

    q: (B, H, d); k/v pools: (P, KV, d, page) — the kernel's model layout;
    block_tables: (B, nb) int32 page ids; lengths: (B,). -> (B, H, d).
    Unallocated table entries hold a valid sentinel page; its stale
    contents sit past ``lengths`` and are masked, so the
    gather-then-attend is exact.
    """
    return decode_attention_ref(q, _pages_head_major(k_pages, block_tables),
                                _pages_head_major(v_pages, block_tables),
                                lengths, rope_theta=rope_theta)


def _pages_head_major(pool: Array, block_tables: Array) -> Array:
    """pool (P, KV, d, page) gathered per row -> (B, KV, nb*page, d)."""
    g = pool[block_tables]                          # (B, nb, KV, d, page)
    b, nb, kv, d, page = g.shape
    return g.transpose(0, 2, 1, 4, 3).reshape(b, kv, nb * page, d)


def prefill_attention_ref(q: Array, k: Array, v: Array, start_len: Array,
                          rope_theta: float | None = None) -> Array:
    """Prefill-chunk flash attention oracle: a C-token chunk against the
    full cache. q: (B, H, C, d); k/v: (B, KV, S, d) — the cache ALREADY
    holds the chunk's keys/values at ``start_len .. start_len + C - 1``;
    start_len: (B,). Chunk token j attends every cache position
    ``<= start_len + j`` (causal within the chunk, full history before it).
    -> (B, H, C, d).

    ``rope_theta``: rotate chunk query j at absolute position
    ``start_len + j`` before attending (the fused-RoPE prefill contract —
    cached keys are already rotated at write time)."""
    b, h, c, d = q.shape
    kv, s = k.shape[1], k.shape[2]
    g = h // kv
    positions = start_len[:, None] + jnp.arange(c)            # (B, C)
    if rope_theta is not None:
        q = rope_ref(q, positions[:, None, :], rope_theta).astype(q.dtype)
    qg = q.reshape(b, kv, g, c, d).astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bkgcd,bksd->bkgcs", qg,
                        k.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None, None, :] <= positions[:, :, None]  # (B,C,S)
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgcs,bksd->bkgcd", p, v.astype(jnp.float32))
    return o.reshape(b, h, c, d).astype(q.dtype)


def paged_prefill_attention_ref(q: Array, k_pages: Array, v_pages: Array,
                                block_tables: Array, start_len: Array,
                                rope_theta: float | None = None) -> Array:
    """Paged prefill-chunk oracle: gather pages, defer to the dense oracle.

    q: (B, H, C, d); k/v pools: (P, KV, d, page); block_tables: (B, nb)
    int32 page ids; start_len: (B,). -> (B, H, C, d)."""
    return prefill_attention_ref(q, _pages_head_major(k_pages, block_tables),
                                 _pages_head_major(v_pages, block_tables),
                                 start_len, rope_theta=rope_theta)


def _latent_rows(pool: Array, block_tables: Array) -> Array:
    """A latent pool (P, 1, W, page) gathered per row -> (B, nb*page, W)."""
    g = pool[block_tables][:, :, 0]                 # (B, nb, W, page)
    b, nb, w, page = g.shape
    return g.transpose(0, 1, 3, 2).reshape(b, nb * page, w)


def _latent_attention(q: Array, rows: Array, qpos: Array, scale: float,
                      rank: int) -> Array:
    """q (B, R, W) against rows (B, S, W); query r sees rows up to
    ``qpos[b, r]``; values are the first ``rank`` columns -> (B, R, rank)."""
    rows = rows.astype(jnp.float32)
    logits = jnp.einsum("brw,bsw->brs", q.astype(jnp.float32), rows) * scale
    valid = jnp.arange(rows.shape[1])[None, None, :] <= qpos[:, :, None]
    p = jax.nn.softmax(jnp.where(valid, logits, NEG_INF), axis=-1)
    return jnp.einsum("brs,bsv->brv", p, rows[..., :rank])


def paged_mla_decode_attention_ref(q: Array, pool: Array,
                                   block_tables: Array, lengths: Array, *,
                                   scale: float, rank: int) -> Array:
    """Paged latent-attention decode oracle. q: (B, H, W) absorbed queries;
    pool: (P, 1, W, page); block_tables: (B, nb); lengths: (B,) keys per
    row. Every head attends the row's first ``lengths`` latent rows, and
    the values are their first ``rank`` columns. -> (B, H, rank)."""
    qpos = jnp.broadcast_to((lengths - 1)[:, None], q.shape[:2])
    return _latent_attention(q, _latent_rows(pool, block_tables), qpos,
                             scale, rank)


def paged_mla_prefill_attention_ref(q: Array, pool: Array,
                                    block_tables: Array, start_len: Array,
                                    *, heads: int, scale: float,
                                    rank: int) -> Array:
    """Paged latent-attention prefill oracle. q: (B, C*H, W) token-major
    (row r is token ``r // heads``); the pool already holds the chunk's
    rows at ``start_len ..``; query row r sees positions up to
    ``start_len + r // heads``. -> (B, C*H, rank)."""
    qpos = start_len[:, None] + jnp.arange(q.shape[1])[None, :] // heads
    return _latent_attention(q, _latent_rows(pool, block_tables), qpos,
                             scale, rank)


def ssd_chunk_ref(x: Array, dt: Array, cum: Array, b_: Array, c_: Array) -> tuple[Array, Array]:
    """Intra-chunk SSD + end-of-chunk state, one chunk.

    x: (Q, H, P); dt: (Q, H); cum: (Q, H) cumulative dt*A within chunk;
    b_/c_: (Q, N) (ngroups=1). Returns (y_intra (Q,H,P), state (H,P,N)).
    """
    q, h, p = x.shape
    xf = x.astype(jnp.float32)
    seg = cum[:, None, :] - cum[None, :, :]                  # (Q, Q, H)
    tri = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(tri[:, :, None], jnp.exp(seg), 0.0)
    cb = jnp.einsum("qn,kn->qk", c_.astype(jnp.float32), b_.astype(jnp.float32))
    scores = cb[:, :, None] * decay * dt[None, :, :]          # (Q, Q, H)
    y = jnp.einsum("qkh,khp->qhp", scores, xf)
    wgt = jnp.exp(cum[-1][None] - cum) * dt                   # (Q, H)
    state = jnp.einsum("qn,qh,qhp->hpn", b_.astype(jnp.float32), wgt, xf)
    return y.astype(x.dtype), state


def rmsnorm_ref(x: Array, w: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)

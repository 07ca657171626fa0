"""Decoder-only transformer LM (dense / moe / vlm families).

Parameters are a nested dict with all per-layer leaves stacked on a leading
layer axis; the forward pass is a ``lax.scan`` over layers with a
configurable remat policy, so the lowered HLO stays compact at any depth
(61-layer kimi-k2 lowers to the same module size as 22-layer tinyllama).
An expert model's leading dense layers (``first_k_dense_replace``) are a
group of their own, ``dense_layers``, scanned before ``layers``; caches
stay indexed by absolute layer.

Attention is grouped-query, or latent (MLA, :mod:`repro.models.mla`) where
the config has a ``kv_lora_rank``: then the cache holds one latent row a
token (``latent``, paged ``latent_pages``) in place of K and V. Expert
layers serve through the held-share dropless layer
(:func:`repro.models.moe.moe_held`), and a paged cache of an expert model
carries ``moe_counts``, the routing counters summed on the device.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, mla, moe
from repro.models.attention import (decode_attention_jnp, flash_attention_jnp,
                                    gather_pages, layer_pages,
                                    naive_attention,
                                    prefill_chunk_attention_jnp)

Array = jax.Array
FLASH_MIN_SEQ = 2048


# ----------------------------------------------------------------- params

def init_attn(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    if cfg.is_mla:
        return mla.init_mla(key, cfg, dtype)
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = layers.split_keys(key, ["q", "k", "v", "o"])
    p = {
        "wq": layers.dense_init(ks["q"], (d, h, hd), dtype=dtype),
        "wk": layers.dense_init(ks["k"], (d, kv, hd), dtype=dtype),
        "wv": layers.dense_init(ks["v"], (d, kv, hd), dtype=dtype),
        "wo": layers.dense_init(ks["o"], (h, hd, d), dtype=dtype),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def init_layer(key, cfg: ModelConfig, dtype=jnp.float32,
               experts: bool | None = None) -> dict:
    """One layer; its FFN holds experts where ``experts`` (default: the
    config is an expert model), else it is the dense MLP of ``d_ff``."""
    ks = layers.split_keys(key, ["attn", "ffn"])
    p = {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": init_attn(ks["attn"], cfg, dtype),
    }
    if cfg.is_moe if experts is None else experts:
        p["ffn"] = moe.init_moe(ks["ffn"], cfg, dtype)
    else:
        p["ffn"] = layers.init_mlp(ks["ffn"], cfg.d_model, cfg.d_ff, dtype)
    return p


def _dense_first(cfg: ModelConfig) -> int:
    """Leading dense layers of an expert model."""
    return cfg.first_k_dense_replace if cfg.is_moe else 0


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ks = layers.split_keys(key, ["emb", "head", "layers"])
    n_dense = _dense_first(cfg)
    lkeys = jax.random.split(ks["layers"], cfg.num_layers - n_dense)
    stacked = jax.vmap(lambda k: init_layer(k, cfg, dtype))(lkeys)
    params = {
        "embedding": layers.init_embedding(ks["emb"], cfg.padded_vocab,
                                           cfg.d_model, dtype),
        "layers": stacked,
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if n_dense:
        dkeys = jax.random.split(jax.random.fold_in(ks["layers"], 1),
                                 n_dense)
        params["dense_layers"] = jax.vmap(
            lambda k: init_layer(k, cfg, dtype, experts=False))(dkeys)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(
            ks["head"], (cfg.d_model, cfg.padded_vocab), dtype=dtype)
    return params


# ----------------------------------------------------------------- pieces

def _project_qkv(p: dict, x: Array, cfg: ModelConfig, positions: Array,
                 rope_q: bool = True):
    """``rope_q=False``: leave q un-rotated — the fused-RoPE decode kernel
    applies the rotation in-kernel (k is always rotated before caching)."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dke->bske", x, p["wk"])
    v = jnp.einsum("bsd,dke->bske", x, p["wv"])
    if cfg.use_qk_norm:
        q = layers.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope_q:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(p: dict, x: Array, cfg: ModelConfig, positions: Array,
                    causal: bool = True):
    """Full-sequence attention. Returns (out, (k, v)) for cache capture."""
    from repro.kernels import ops
    q, k, v = _project_qkv(p, x, cfg, positions)
    from repro.distributed import hints
    if hints.get("attn_impl") == "repeat_kv" and cfg.num_kv_heads < cfg.num_heads:
        g = cfg.num_heads // cfg.num_kv_heads
        k_r = jnp.repeat(k, g, axis=2)
        v_r = jnp.repeat(v, g, axis=2)
        if x.shape[1] >= FLASH_MIN_SEQ:
            o = flash_attention_jnp(q, k_r, v_r, causal=causal)
        else:
            o = naive_attention(q, k_r, v_r, causal=causal)
        out = jnp.einsum("bshe,hed->bsd", o, p["wo"])
        return out, (k, v)
    if hints.get("attn_kv_replicated"):
        # GQA blocking stays model-local: gather the (small) k/v heads ONCE
        # per layer instead of per-q-block reshard gathers (hillclimb).
        from jax.sharding import PartitionSpec as P
        try:
            dp = tuple(a for a in ("pod", "data")
                       if a in jax.sharding.get_abstract_mesh().axis_names)
            bspec = (dp if len(dp) > 1 else dp[0]) if dp else None
            k = jax.lax.with_sharding_constraint(k, P(bspec, None, None, None))
            v = jax.lax.with_sharding_constraint(v, P(bspec, None, None, None))
            h_ax = "model" if cfg.num_heads % 16 == 0 else None
            q = jax.lax.with_sharding_constraint(q, P(bspec, None, h_ax, None))
        except Exception:
            pass
    if ops.backend() != "jnp":
        o = ops.attention_prefill(q, k, v, causal=causal)
    elif x.shape[1] >= FLASH_MIN_SEQ:
        o = flash_attention_jnp(q, k, v, causal=causal)
    else:
        o = naive_attention(q, k, v, causal=causal)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"])
    return out, (k, v)


def _quantize_kv(t: Array) -> tuple[Array, Array]:
    """t: (B, KV, hd) -> (int8 values, per-(B,KV) scale)."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def attention_decode_block(p: dict, x: Array, cfg: ModelConfig,
                           k_cache: Array, v_cache: Array, lengths: Array,
                           k_scale: Array | None = None,
                           v_scale: Array | None = None,
                           active: Array | None = None):
    """One-token attention against a cache.

    x: (B,1,D); caches: (B,S,KV,hd) bf16 — or int8 with per-(B,S,KV) scales
    (hillclimb hint ``kv_cache_dtype=int8``: halves decode cache traffic).
    Writes the new k/v at position ``lengths``, attends over ``lengths+1``.

    ``active``: optional (B,) bool slot mask. Inactive rows write nothing —
    their write position is pushed past the cache end so the ``mode="drop"``
    scatter discards it (length-masked writes: zero extra copies, unlike the
    old per-slot save/restore). Their outputs are garbage and must be
    ignored by the caller. RoPE on q is fused into the decode attention
    (ops.attention_decode / decode_attention_jnp), not a separate op here.
    """
    positions = lengths[:, None]  # (B,1) absolute position of the new token
    q, k, v = _project_qkv(p, x, cfg, positions, rope_q=False)

    b = x.shape[0]
    s = k_cache.shape[1]
    bidx = jnp.arange(b)
    w_pos = lengths if active is None else \
        jnp.where(active, lengths, jnp.int32(s))
    int8_kv = k_scale is not None
    if int8_kv:
        kq, ks = _quantize_kv(k[:, 0])
        vq, vs = _quantize_kv(v[:, 0])
        k_cache = k_cache.at[bidx, w_pos].set(kq, mode="drop")
        v_cache = v_cache.at[bidx, w_pos].set(vq, mode="drop")
        k_scale = k_scale.at[bidx, w_pos].set(ks, mode="drop")
        v_scale = v_scale.at[bidx, w_pos].set(vs, mode="drop")
        k_full = (k_cache.astype(jnp.bfloat16) *
                  k_scale[..., None].astype(jnp.bfloat16))
        v_full = (v_cache.astype(jnp.bfloat16) *
                  v_scale[..., None].astype(jnp.bfloat16))
    else:
        k_cache = k_cache.at[bidx, w_pos].set(
            k[:, 0].astype(k_cache.dtype), mode="drop")
        v_cache = v_cache.at[bidx, w_pos].set(
            v[:, 0].astype(v_cache.dtype), mode="drop")
        k_full, v_full = k_cache, v_cache
    from repro.kernels import ops
    if ops.backend() != "jnp":
        o = ops.attention_decode(q, k_full, v_full, lengths + 1,
                                 rope_theta=cfg.rope_theta)
    else:
        o = decode_attention_jnp(q, k_full, v_full, lengths + 1,
                                 rope_theta=cfg.rope_theta)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"])
    if int8_kv:
        return out, (k_cache, v_cache, k_scale, v_scale)
    return out, (k_cache, v_cache)


def attention_decode_block_paged(p: dict, x: Array, cfg: ModelConfig,
                                 k_pages: Array, v_pages: Array,
                                 block_tables: Array, lengths: Array,
                                 active: Array | None = None,
                                 layer: Array | int = 0):
    """One-token attention against a PAGED cache.

    x: (B,1,D); pools: the whole stack's (L, P, KV, hd, page), read and
    written at ``layer``, or one layer's (P, KV, hd, page) slab (the pool's
    rank says which), shared across rows; block_tables: (B, nb) int32 page
    ids. The new k/v lands in the page covering position ``lengths`` (the
    engine maps that page before dispatch); attention gathers K/V through
    the block table (``ops.attention_decode_paged`` — Pallas scalar-prefetch
    gather on TPU, materialized gather on jnp). A layer scan that carries
    the whole pool updates it in place: one (hd,) column per row and
    layer is written, and no slab is sliced or copied.

    ``active``: inactive rows write nothing. Same contract as
    :func:`attention_decode_block`; no int8 path (the engine falls back to
    the contiguous cache under ``kv_cache_dtype`` hints).
    """
    positions = lengths[:, None]
    q, k, v = _project_qkv(p, x, cfg, positions, rope_q=False)

    page = k_pages.shape[-1]
    block = jnp.minimum(lengths // page, block_tables.shape[1] - 1)
    pidx = jnp.take_along_axis(block_tables, block[:, None], axis=1)[:, 0]
    write = jnp.ones(lengths.shape, bool) if active is None else active
    k_pages = _write_tokens(k_pages, layer, pidx, lengths % page, k[:, 0],
                            write)
    v_pages = _write_tokens(v_pages, layer, pidx, lengths % page, v[:, 0],
                            write)
    from repro.kernels import ops
    o = ops.attention_decode_paged(q, k_pages, v_pages, block_tables,
                                   lengths + 1, rope_theta=cfg.rope_theta,
                                   layer=layer)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"])
    return out, (k_pages, v_pages)


def _write_tokens(pages: Array, layer, pidx: Array, off: Array,
                  new: Array, write: Array) -> Array:
    """Write one token per row: ``new[b]`` (KV, hd) as the column at
    offset ``off[b]`` of page ``pidx[b]``, where ``write[b]``; other rows
    write their column back unchanged. A token is one column of a
    transposed (hd, page) page, so each row is a dynamic-update-slice of
    that column in place: a scatter of the columns would lay the pool out
    with hd innermost, and XLA would copy the pool there and back at every
    layer. ``pages``: (L, P, KV, hd, page) at ``layer``, or one layer's
    (P, KV, hd, page)."""
    pool = pages if pages.ndim == 5 else pages[None]
    lay = layer if pages.ndim == 5 else 0
    size = (1, 1) + pool.shape[2:4] + (1,)
    for b in range(new.shape[0]):
        at = (lay, pidx[b], 0, 0, off[b])
        old = jax.lax.dynamic_slice(pool, at, size)
        col = new[b].astype(pool.dtype)[None, None, :, :, None]
        pool = jax.lax.dynamic_update_slice(
            pool, jnp.where(write[b], col, old), at)
    return pool if pages.ndim == 5 else pool[0]


def _write_chunk(pages: Array, layer, block_tables: Array, start: Array,
                 new: Array, write: Array) -> Array:
    """Write each row's chunk: ``new[b, c]`` (KV, hd) at position
    ``start[b] + c``, where ``write[b, c]``. A chunk's columns can straddle
    two pages, so no single column block fits: each page a writing row's
    chunk can touch is read, merged on the token axis and written back
    whole (see :func:`_write_tokens` for why not a scatter). One loop step
    per row and page, over the rows that write anything: a prefill
    dispatch of one live row among many touches one row's pages, and the
    program stays one loop body long whatever the batch. ``pages`` as in
    :func:`_write_tokens`."""
    pool = pages if pages.ndim == 5 else pages[None]
    lay = layer if pages.ndim == 5 else 0
    c = write.shape[1]
    kv, hd, page = pool.shape[2:]
    nb = block_tables.shape[1]
    span = (c + page - 2) // page + 1            # pages a chunk can touch
    writes = write.any(axis=1)
    rows = jnp.argsort(~writes)                  # writing rows first
    # token c of row b at column page + c: a page's worth of slack each
    # side, so the columns of every page a chunk touches slice out whole
    pad = ((0, 0), (page, page))
    cols = jnp.pad(new.astype(pool.dtype).transpose(0, 2, 3, 1),
                   ((0, 0), (0, 0)) + pad)                # (B, KV, hd, ...)
    mask = jnp.pad(write, pad)

    def one(i, pool):
        r, t = rows[i // span], i % span
        blk = start[r] // page + t
        at = (lay, block_tables[r, jnp.minimum(blk, nb - 1)], 0, 0, 0)
        old = jax.lax.dynamic_slice(pool, at, (1, 1, kv, hd, page))
        lo = blk * page - start[r] + page
        tok = jax.lax.dynamic_slice(cols, (r, 0, 0, lo), (1, kv, hd, page))
        hit = jax.lax.dynamic_slice(mask, (r, lo), (1, page))
        merged = jnp.where(hit[:, None, None, :], tok, old[0])
        return jax.lax.dynamic_update_slice(pool, merged[None], at)

    pool = jax.lax.fori_loop(0, writes.sum() * span, one, pool)
    return pool if pages.ndim == 5 else pool[0]


def mla_decode_block_paged(p: dict, x: Array, cfg: ModelConfig, pool: Array,
                           block_tables: Array, lengths: Array,
                           active: Array | None = None,
                           layer: Array | int = 0):
    """One-token latent attention against the PAGED latent pool, in
    absorbed form: the token's latent row is written as one column of the
    page covering ``lengths`` (as :func:`attention_decode_block_paged`
    writes K), and every head attends the row's latent pages
    (``ops.attention_mla_decode_paged``). pool: (L, P, 1, W, page) at
    ``layer``, or one layer's slab."""
    q_abs, lat = mla.project(p, x, cfg, lengths[:, None])
    page = pool.shape[-1]
    block = jnp.minimum(lengths // page, block_tables.shape[1] - 1)
    pidx = jnp.take_along_axis(block_tables, block[:, None], axis=1)[:, 0]
    write = jnp.ones(lengths.shape, bool) if active is None else active
    pool = _write_tokens(pool, layer, pidx, lengths % page, lat[:, :1],
                         write)
    from repro.kernels import ops
    o = ops.attention_mla_decode_paged(q_abs, pool, block_tables,
                                       lengths + 1, scale=mla.scale(cfg),
                                       rank=cfg.kv_lora_rank, layer=layer)
    return mla.out(p, o, cfg, x.dtype), pool


def mla_prefill_chunk_block_paged(p: dict, x: Array, cfg: ModelConfig,
                                  pool: Array, block_tables: Array,
                                  start_len: Array,
                                  active: Array | None = None,
                                  valid: Array | None = None,
                                  layer: Array | int = 0):
    """Chunked-prefill latent attention against the PAGED latent pool: the
    chunk's latent rows are written into the rows' pages (pad tokens under
    ``valid`` write nothing), then the absorbed queries attend causally
    (``ops.attention_mla_prefill_paged``)."""
    b, c, _ = x.shape
    positions = start_len[:, None] + jnp.arange(c)[None, :]
    q_abs, lat = mla.project(p, x, cfg, positions)
    write = jnp.ones((b, c), bool)
    if active is not None:
        write = write & active[:, None]
    if valid is not None:
        write = write & (jnp.arange(c)[None, :] < valid[:, None])
    pool = _write_chunk(pool, layer, block_tables, start_len,
                        lat[:, :, None, :], write)
    from repro.kernels import ops
    o = ops.attention_mla_prefill_paged(q_abs, pool, block_tables,
                                        start_len, scale=mla.scale(cfg),
                                        rank=cfg.kv_lora_rank, layer=layer)
    return mla.out(p, o, cfg, x.dtype), pool


def mla_chunk_block(p: dict, x: Array, cfg: ModelConfig, cache: Array,
                    start_len: Array, active: Array | None = None,
                    valid: Array | None = None):
    """Latent attention of C new tokens against a contiguous (B, S, W)
    latent cache: the rows are written at ``start_len ..`` (inactive rows
    and pad tokens dropped, as in :func:`attention_prefill_chunk_block`),
    then attended causally. C = 1 is a decode step."""
    b, c, _ = x.shape
    s = cache.shape[1]
    positions = start_len[:, None] + jnp.arange(c)[None, :]
    q_abs, lat = mla.project(p, x, cfg, positions)
    w_pos = positions if active is None else \
        jnp.where(active[:, None], positions, jnp.int32(s))
    if valid is not None:
        w_pos = jnp.where(jnp.arange(c)[None, :] < valid[:, None], w_pos,
                          jnp.int32(s))
    cache = cache.at[jnp.arange(b)[:, None], w_pos].set(
        lat.astype(cache.dtype), mode="drop")
    o = mla.attend(q_abs, cache, positions, mla.scale(cfg), cfg.kv_lora_rank)
    return mla.out(p, o, cfg, x.dtype), cache


def _chunk_attend(p: dict, q: Array, k_full: Array, v_full: Array,
                  positions: Array, cfg: ModelConfig, x_dtype) -> Array:
    """Chunk-vs-cache causal attention shared by the contiguous and paged
    prefill paths. q: (B,C,H,hd) UN-rotated (RoPE is fused into the
    attention — in-kernel on the Pallas path, ``apply_rope`` first thing on
    the jnp path); k_full/v_full: (B,S,KV,hd); positions: (B,C) absolute
    position per chunk token."""
    from repro.kernels import ops
    if ops.backend() != "jnp":
        o = ops.attention_prefill_chunk(q, k_full, v_full, positions[:, 0],
                                        rope_theta=cfg.rope_theta)
    else:
        o = prefill_chunk_attention_jnp(q, k_full, v_full, positions,
                                        rope_theta=cfg.rope_theta)
    o = o.astype(x_dtype)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"])


def attention_prefill_chunk_block_paged(p: dict, x: Array, cfg: ModelConfig,
                                        k_pages: Array, v_pages: Array,
                                        block_tables: Array, start_len: Array,
                                        active: Array | None = None,
                                        valid: Array | None = None,
                                        layer: Array | int = 0):
    """Chunked-prefill attention against a PAGED cache: C new tokens are
    scattered into their rows' pages (positions ``start_len ..
    start_len+C-1`` resolved through the block table) and attended causally
    over the gathered padded view. Same semantics as
    :func:`attention_prefill_chunk_block` with the cache paged (pad-token
    tokens write nothing under ``valid``). The pools are the whole stack's
    at ``layer`` or one layer's slab, as in
    :func:`attention_decode_block_paged`."""
    b, c, _ = x.shape
    positions = start_len[:, None] + jnp.arange(c)[None, :]       # (B,C)
    q, k, v = _project_qkv(p, x, cfg, positions, rope_q=False)

    write = jnp.ones((b, c), bool)
    if active is not None:
        write = write & active[:, None]
    if valid is not None:
        write = write & (jnp.arange(c)[None, :] < valid[:, None])
    k_pages = _write_chunk(k_pages, layer, block_tables, start_len, k, write)
    v_pages = _write_chunk(v_pages, layer, block_tables, start_len, v, write)

    from repro.kernels import ops
    if ops.backend() != "jnp":
        # stream pages through the block table in-kernel — never gather
        o = ops.attention_prefill_chunk_paged(q, k_pages, v_pages,
                                              block_tables, start_len,
                                              rope_theta=cfg.rope_theta,
                                              layer=layer)
        out = jnp.einsum("bshe,hed->bsd", o.astype(x.dtype), p["wo"])
        return out, (k_pages, v_pages)
    k_full = gather_pages(layer_pages(k_pages, layer), block_tables)
    v_full = gather_pages(layer_pages(v_pages, layer), block_tables)
    out = _chunk_attend(p, q, k_full, v_full, positions, cfg, x.dtype)
    return out, (k_pages, v_pages)


def attention_prefill_chunk_block(p: dict, x: Array, cfg: ModelConfig,
                                  k_cache: Array, v_cache: Array,
                                  start_len: Array,
                                  k_scale: Array | None = None,
                                  v_scale: Array | None = None,
                                  active: Array | None = None,
                                  valid: Array | None = None):
    """Chunked-prefill attention: C new tokens against cache + themselves.

    x: (B,C,D); caches: (B,S,KV,hd); start_len: (B,) tokens already in the
    cache per row. Writes the chunk's k/v at ``start_len .. start_len+C-1``
    (length-masked scatter; inactive rows dropped, same contract as
    :func:`attention_decode_block`) and attends causally over the whole
    padded cache — ONE dispatch for the whole chunk instead of C.

    ``valid``: optional (B,) per-row count of real chunk tokens — rows
    shorter than C are padded at the tail (multi-slot batched prefill
    advancing several mid-prefill slots by different amounts in one
    dispatch). Pad tokens' writes are pushed past the cache end (dropped),
    and their attention outputs are garbage the caller must ignore; valid
    tokens only ever attend to positions ``<= start_len + j``, all real.
    ``valid=None`` keeps the full-width path bit-identical.
    """
    b, c, _ = x.shape
    s = k_cache.shape[1]
    positions = start_len[:, None] + jnp.arange(c)[None, :]       # (B,C)
    q, k, v = _project_qkv(p, x, cfg, positions, rope_q=False)

    w_start = start_len if active is None else \
        jnp.where(active, start_len, jnp.int32(s))
    w_pos = w_start[:, None] + jnp.arange(c)[None, :]             # (B,C)
    if valid is not None:
        tok_ok = jnp.arange(c)[None, :] < valid[:, None]          # (B,C)
        w_pos = jnp.where(tok_ok, w_pos, jnp.int32(s))
    bidx = jnp.arange(b)[:, None]
    int8_kv = k_scale is not None
    if int8_kv:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        k_cache = k_cache.at[bidx, w_pos].set(kq, mode="drop")
        v_cache = v_cache.at[bidx, w_pos].set(vq, mode="drop")
        k_scale = k_scale.at[bidx, w_pos].set(ks, mode="drop")
        v_scale = v_scale.at[bidx, w_pos].set(vs, mode="drop")
        k_full = (k_cache.astype(jnp.bfloat16) *
                  k_scale[..., None].astype(jnp.bfloat16))
        v_full = (v_cache.astype(jnp.bfloat16) *
                  v_scale[..., None].astype(jnp.bfloat16))
    else:
        k_cache = k_cache.at[bidx, w_pos].set(
            k.astype(k_cache.dtype), mode="drop")
        v_cache = v_cache.at[bidx, w_pos].set(
            v.astype(v_cache.dtype), mode="drop")
        k_full, v_full = k_cache, v_cache

    out = _chunk_attend(p, q, k_full, v_full, positions, cfg, x.dtype)
    if int8_kv:
        return out, (k_cache, v_cache, k_scale, v_scale)
    return out, (k_cache, v_cache)


def _ffn(p: dict, x: Array, cfg: ModelConfig,
         token_mask: Array | None = None, serving: bool = False):
    """(y, aux, routing counts); the layer's params say whether it holds
    experts. ``serving``: experts run dropless (see ``moe.moe_dispatch``)."""
    if "router" in p:
        return moe.moe_dispatch(p, x, cfg, token_mask, dropless=serving)
    return (layers.mlp(p, x), jnp.zeros((), jnp.float32),
            jnp.zeros((3,), jnp.int32))


def _scan_layers(body, carry, params: dict, per_layer=()):
    """``body(carry, (lp, l, slices)) -> (carry, ys)`` over every layer:
    the leading dense group (``dense_layers``) first, then ``layers``.
    ``l`` is the absolute layer index, and ``per_layer`` (arrays stacked
    over all layers, such as a contiguous cache) is sliced to each group.
    Returns the last carry and the ys stacked over all layers."""
    outs, lo = [], 0
    for name in ("dense_layers", "layers"):
        if name not in params:
            continue
        group = params[name]
        n = jax.tree.leaves(group)[0].shape[0]
        xs = (group, jnp.arange(lo, lo + n),
              tuple(a[lo:lo + n] for a in per_layer))
        carry, ys = layers.scan(body, carry, xs)
        outs.append(ys)
        lo += n
    if len(outs) == 1:
        return carry, outs[0]
    return carry, jax.tree.map(lambda *a: jnp.concatenate(a), *outs)


def _pool_keys(cfg: ModelConfig) -> tuple:
    """The paged cache's pools, in the order the layer scan carries them."""
    return ("latent_pages",) if cfg.is_mla else ("k_pages", "v_pages")


def _logits(params: dict, x: Array, cfg: ModelConfig) -> Array:
    """Final norm and unembedding."""
    x = layers.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return layers.unembed(x, params["embedding"], transpose=True)
    return layers.unembed(x, params["lm_head"], transpose=False)


# ---------------------------------------------------------------- forward

def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)  # "full": save only layer inputs


def _residual_constraint(x: Array) -> Array:
    from repro.distributed import hints
    if not hints.get("residual_replicated"):
        return x
    try:
        from jax.sharding import PartitionSpec as P
        mesh = jax.sharding.get_abstract_mesh()
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        bspec = (dp if len(dp) > 1 else dp[0]) if dp else None
        return jax.lax.with_sharding_constraint(x, P(bspec, None, None))
    except Exception:
        return x


def forward(params: dict, tokens: Array, cfg: ModelConfig, *,
            remat: str = "full", embeds: Array | None = None,
            causal: bool = True, return_cache: bool = False):
    """tokens: (B, S) int32 (or ``embeds``: (B,S,D) for frontend stubs).

    Returns (logits, aux_loss) or (logits, aux_loss, cache) with
    cache = {"k": (L,B,S,KV,hd), "v": ...} (MLA: {"latent": (L,B,S,W)})
    when ``return_cache``.
    """
    x = embeds if embeds is not None else layers.embed(params["embedding"], tokens)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s)[None, :]

    def body(carry, inp):
        x, aux = carry
        lp = inp[0]
        h = layers.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if cfg.is_mla:
            attn_out, kv = mla.attention_block(lp["attn"], h, cfg, positions)
        else:
            attn_out, kv = attention_block(lp["attn"], h, cfg, positions,
                                           causal)
        x = _residual_constraint(x + attn_out)
        h2 = layers.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        ffn_out, a, _ = _ffn(lp["ffn"], h2, cfg)
        x = _residual_constraint(x + ffn_out)
        return (x, aux + a), kv if return_cache else None

    body = _remat(body, remat)
    (x, aux), kv = _scan_layers(body, (x, jnp.zeros((), jnp.float32)),
                                params)
    logits = _logits(params, x, cfg)
    if return_cache:
        cache = {"latent": kv} if cfg.is_mla else {"k": kv[0], "v": kv[1]}
        return logits, aux, cache
    return logits, aux


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> dict:
    from repro.distributed import hints
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    l = cfg.num_layers
    if cfg.is_mla:
        return {"latent": jnp.zeros((l, batch, max_seq, cfg.latent_width),
                                    dtype)}
    if hints.get("kv_cache_dtype") == "int8":
        return {
            "k": jnp.zeros((l, batch, max_seq, kv, hd), jnp.int8),
            "v": jnp.zeros((l, batch, max_seq, kv, hd), jnp.int8),
            "k_scale": jnp.zeros((l, batch, max_seq, kv), jnp.bfloat16),
            "v_scale": jnp.zeros((l, batch, max_seq, kv), jnp.bfloat16),
        }
    return {
        "k": jnp.zeros((l, batch, max_seq, kv, hd), dtype),
        "v": jnp.zeros((l, batch, max_seq, kv, hd), dtype),
    }


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> dict:
    """Page-pool KV cache: ``num_pages`` shared pages of ``page_size``
    tokens per layer, head-major and transposed, ``(L, P, KV, hd, page)``,
    so a page's per-head ``(hd, page)`` slab is one tile of the paged
    kernels, held unpadded in the device's default layout (see
    :mod:`repro.kernels.paged_decode_attention`); rows address pages
    through engine-side block tables. MLA holds one latent head,
    ``latent_pages`` ``(L, P, 1, W, page)``. An expert model's cache also
    carries ``moe_counts``, int32 (picks, held picks, busiest held
    expert's picks) summed by every step program. No int8 variant — the
    engine keeps the contiguous cache under ``kv_cache_dtype`` hints."""
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    l = cfg.num_layers
    if cfg.is_mla:
        cache = {"latent_pages": jnp.zeros(
            (l, num_pages, 1, cfg.latent_width, page_size), dtype)}
    else:
        cache = {
            "k_pages": jnp.zeros((l, num_pages, kv, hd, page_size), dtype),
            "v_pages": jnp.zeros((l, num_pages, kv, hd, page_size), dtype),
        }
    if cfg.is_moe:
        cache["moe_counts"] = jnp.zeros((3,), jnp.int32)
    return cache


def prefill(params: dict, tokens: Array, cfg: ModelConfig, max_seq: int,
            embeds: Array | None = None):
    """Run the full prompt; return (logits, cache padded to max_seq)."""
    logits, _, cache = forward(params, tokens, cfg, remat="none",
                               embeds=embeds, return_cache=True)
    s = tokens.shape[1] if tokens is not None else embeds.shape[1]

    def pad(v):
        v = v.astype(jnp.bfloat16)
        if max_seq <= s:
            return v
        return jnp.pad(v, [(0, 0), (0, 0), (0, max_seq - s)]
                       + [(0, 0)] * (v.ndim - 3))
    return logits, {k: pad(v) for k, v in cache.items()}


def decode_step(params: dict, cache: dict, tokens: Array, lengths: Array,
                cfg: ModelConfig, active: Array | None = None):
    """One decode step. tokens: (B,1); lengths: (B,).

    Returns (logits (B, V), new_cache). ``active``: optional (B,) bool mask;
    inactive rows leave the cache untouched (mask-isolated decode — the
    serving engine threads its slot mask here instead of saving/restoring
    per-slot cache slices around every step).
    """
    x = layers.embed(params["embedding"], tokens)
    int8_kv = "k_scale" in cache
    names = (("latent",) if cfg.is_mla else
             ("k", "v", "k_scale", "v_scale") if int8_kv else ("k", "v"))

    def body(x, inp):
        lp, _, caches = inp
        h = layers.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if cfg.is_mla:
            attn_out, lat = mla_chunk_block(lp["attn"], h, cfg, caches[0],
                                            lengths, active)
            caches = (lat,)
        else:
            kc, vc = caches[:2]
            ks, vs = caches[2:] if int8_kv else (None, None)
            attn_out, caches = attention_decode_block(
                lp["attn"], h, cfg, kc, vc, lengths, ks, vs, active=active)
        x = x + attn_out
        h2 = layers.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        ffn_out, _, _ = _ffn(lp["ffn"], h2, cfg, token_mask=active,
                             serving=True)
        x = x + ffn_out
        return x, caches

    x, new = _scan_layers(body, x, params, tuple(cache[n] for n in names))
    logits = _logits(params, x, cfg)
    return logits[:, 0], dict(zip(names, new))


def _live(tokens: Array, active: Array | None, valid: Array | None):
    """The (B, C) mask of a chunk's real tokens (the row is active, the
    token not a pad under ``valid``), or ``active`` itself without
    ``valid``."""
    if valid is None:
        return active
    live = jnp.arange(tokens.shape[1])[None, :] < valid[:, None]
    return live if active is None else live & active[:, None]


def _scope(name: str, on: bool):
    """A ``jax.named_scope`` where ``on``, so that a profile shows it."""
    return jax.named_scope(name) if on else contextlib.nullcontext()


def _paged_step(params: dict, cache: dict, tokens: Array, cfg: ModelConfig,
                attend, active: Array | None):
    """The layer scan of a paged step: the pools and the routing counters
    ride the carry, each layer updating the pools in place at its own index
    through ``attend(p, h, pools, layer) -> (out, pools)``, so a step moves
    no pool-sized slab (with the pool donated, not one copy). Latent
    attention and expert layers run under the named scopes ``mla`` and
    ``moe``. Returns the final hidden states and the new cache."""
    x = layers.embed(params["embedding"], tokens)
    keys = _pool_keys(cfg)

    def body(carry, inp):
        x, pools, counts = carry
        lp, l, _ = inp
        h = layers.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        with _scope("mla", cfg.is_mla):
            attn_out, pools = attend(lp["attn"], h, pools, l)
        x = x + attn_out
        h2 = layers.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        with _scope("moe", "router" in lp["ffn"]):
            ffn_out, _, c = _ffn(lp["ffn"], h2, cfg, token_mask=active,
                                 serving=True)
        x = x + ffn_out
        return (x, pools, counts + c), None

    pools = tuple(cache[k] for k in keys)
    (x, pools, counts), _ = _scan_layers(
        body, (x, pools, jnp.zeros((3,), jnp.int32)), params)
    new = dict(zip(keys, pools))
    if "moe_counts" in cache:
        new["moe_counts"] = cache["moe_counts"] + counts
    return x, new


def decode_step_paged(params: dict, cache: dict, tokens: Array,
                      lengths: Array, block_tables: Array,
                      cfg: ModelConfig, active: Array | None = None):
    """One decode step against the paged cache. tokens: (B,1); lengths:
    (B,); block_tables: (B, nb). Same contract as :func:`decode_step`
    (logits (B,V), new cache; inactive rows untouched), with K/V (or the
    latent rows) written into and gathered from the shared page pool by
    :func:`_paged_step`."""
    def attend(p, h, pools, layer):
        if cfg.is_mla:
            out, pool = mla_decode_block_paged(p, h, cfg, pools[0],
                                               block_tables, lengths,
                                               active=active, layer=layer)
            return out, (pool,)
        return attention_decode_block_paged(p, h, cfg, *pools, block_tables,
                                            lengths, active=active,
                                            layer=layer)
    x, new = _paged_step(params, cache, tokens, cfg, attend, active)
    return _logits(params, x, cfg)[:, 0], new


def prefill_chunk_paged(params: dict, cache: dict, tokens: Array,
                        start_len: Array, block_tables: Array,
                        cfg: ModelConfig, active: Array | None = None,
                        valid: Array | None = None):
    """Batched chunked prefill against the paged cache; see
    :func:`prefill_chunk` for the contract. The pool rides the layer scan's
    carry and is updated in place, as in :func:`decode_step_paged`."""
    def attend(p, h, pools, layer):
        if cfg.is_mla:
            out, pool = mla_prefill_chunk_block_paged(
                p, h, cfg, pools[0], block_tables, start_len, active=active,
                valid=valid, layer=layer)
            return out, (pool,)
        return attention_prefill_chunk_block_paged(
            p, h, cfg, *pools, block_tables, start_len, active=active,
            valid=valid, layer=layer)
    x, new = _paged_step(params, cache, tokens, cfg, attend,
                         _live(tokens, active, valid))
    return _logits(params, x, cfg), new


def prefill_chunk(params: dict, cache: dict, tokens: Array, start_len: Array,
                  cfg: ModelConfig, active: Array | None = None,
                  valid: Array | None = None):
    """Batched chunked prefill: advance every row by C tokens in ONE pass.

    tokens: (B,C); start_len: (B,) tokens already cached per row. Returns
    (logits (B,C,V), new_cache). Replaces the serving engine's
    token-at-a-time prefill loop (C jitted dispatches) with one dispatch;
    parity with the token-stepped path is pinned in tests/test_serving.py.
    Rows with ``active=False`` keep their cache bit-identical.

    ``valid``: optional (B,) real-token count per row (pads at the tail) —
    multi-slot batched prefill, where one dispatch advances several
    mid-prefill slots by different amounts. Pad tokens write nothing; their
    logits are garbage the engine discards.
    """
    x = layers.embed(params["embedding"], tokens)
    int8_kv = "k_scale" in cache
    names = (("latent",) if cfg.is_mla else
             ("k", "v", "k_scale", "v_scale") if int8_kv else ("k", "v"))

    def body(x, inp):
        lp, _, caches = inp
        h = layers.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if cfg.is_mla:
            attn_out, lat = mla_chunk_block(lp["attn"], h, cfg, caches[0],
                                            start_len, active, valid)
            caches = (lat,)
        else:
            kc, vc = caches[:2]
            ks, vs = caches[2:] if int8_kv else (None, None)
            attn_out, caches = attention_prefill_chunk_block(
                lp["attn"], h, cfg, kc, vc, start_len, ks, vs, active=active,
                valid=valid)
        x = x + attn_out
        h2 = layers.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        ffn_out, _, _ = _ffn(lp["ffn"], h2, cfg,
                             token_mask=_live(tokens, active, valid),
                             serving=True)
        x = x + ffn_out
        return x, caches

    x, new = _scan_layers(body, x, params, tuple(cache[n] for n in names))
    logits = _logits(params, x, cfg)
    return logits, dict(zip(names, new))

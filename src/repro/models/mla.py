"""Latent attention (MLA, DeepSeek-V2/V3 without q LoRA) in absorbed form.

A layer projects each token to a query of ``qk_nope_head_dim +
qk_rope_head_dim`` per head and to one cached row of ``latent_width``
columns: the RMS-normed KV latent ``c`` (``kv_lora_rank``) followed by one
rotary key ``k_pe`` shared by every head. ``wkv_b`` (latent, heads,
nope + v) holds the up-projections: its first ``qk_nope_head_dim`` columns
give each head's key from the latent (``W_UK``), the rest its value
(``W_UV``).

Absorbed, nothing per head is ever cached or expanded: a head's query is
``[q_nope @ W_UK^T, rope(q_pe)]``, ``latent_width`` wide, and it attends
the latent rows directly at scale ``1/sqrt(nope + rope)``, since
``q_nope . (c @ W_UK) = (q_nope @ W_UK^T) . c``. The values are the
latent's first ``kv_lora_rank`` columns; the weighted latent goes back
through ``W_UV`` and then ``wo``. Rotary embedding is split-half over the
64 rotary columns (the published weights pair them interleaved, a fixed
permutation of those columns).

The cache holds the rows: contiguous ``(L, B, S, W)``, or paged in a pool of
one latent head, ``(L, P, 1, W, page)``, pages held transposed as the dense
pools are, so the paged writes and gathers of :mod:`repro.models.transformer`
serve it unchanged. Attention runs in ``repro.kernels`` on the chip
(``paged_mla_decode_attention``, ``paged_mla_prefill_attention``) and in
:func:`attend` elsewhere.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers

Array = jax.Array
NEG_INF = -1e30


def init_mla(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = layers.split_keys(key, ["q", "kv_a", "kv_b", "o"])
    return {
        "wq": layers.dense_init(ks["q"], (d, h, nope + rope), dtype=dtype),
        "wkv_a": layers.dense_init(ks["kv_a"], (d, r + rope), dtype=dtype),
        "kv_norm": jnp.ones((r,), jnp.float32),
        "wkv_b": layers.dense_init(ks["kv_b"], (r, h, nope + vd), dtype=dtype),
        "wo": layers.dense_init(ks["o"], (h, vd, d), dtype=dtype),
    }


def scale(cfg: ModelConfig) -> float:
    """The softmax scale of a query head of ``nope + rope`` columns."""
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def project(p: dict, x: Array, cfg: ModelConfig, positions: Array):
    """x (B, S, D), positions (B|1, S) -> the absorbed queries (B, S, H, W)
    in float32 and the tokens' latent rows (B, S, W) in x's type."""
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    kv = jnp.einsum("bsd,de->bse", x, p["wkv_a"])
    c = layers.rmsnorm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = layers.apply_rope(kv[..., None, r:], positions, cfg.rope_theta)
    q_pe = layers.apply_rope(q[..., nope:], positions, cfg.rope_theta)
    q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :nope],
                       p["wkv_b"][..., :nope],
                       preferred_element_type=jnp.float32)
    q_abs = jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], axis=-1)
    return q_abs, jnp.concatenate([c, k_pe[..., 0, :]], axis=-1)


def out(p: dict, o_lat: Array, cfg: ModelConfig, dtype) -> Array:
    """Weighted latents (B, S, H, kv_lora_rank) -> (B, S, D): through each
    head's value up-projection, then ``wo``."""
    v = p["wkv_b"][..., cfg.qk_nope_head_dim:]
    o = jnp.einsum("bshr,rhv->bshv", o_lat.astype(dtype), v)
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"])


def attend(q_abs: Array, latent: Array, q_pos: Array, scale: float,
           rank: int) -> Array:
    """Absorbed attention (jnp): q_abs (B, C, H, W) against latent rows
    (B, S, W) whose first ``rank`` columns are the values; query c sees
    rows ``0 .. q_pos[b, c]``. Returns the weighted latents (B, C, H, rank)
    in float32. Materializes the (B, H, C, S) scores: the CPU and test
    path."""
    lat = latent.astype(jnp.float32)
    s = jnp.einsum("bchw,bsw->bhcs", q_abs.astype(jnp.float32), lat) * scale
    seen = jnp.arange(lat.shape[1])[None, None, :] <= q_pos[:, :, None]
    s = jnp.where(seen[:, None], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhcs,bsr->bchr", pr, lat[..., :rank])


def attention_block(p: dict, x: Array, cfg: ModelConfig, positions: Array):
    """Full-sequence causal MLA. Returns (out, latent rows) for cache
    capture."""
    q_abs, lat = project(p, x, cfg, positions)
    s = x.shape[1]
    q_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (x.shape[0], s))
    o_lat = attend(q_abs, lat, q_pos, scale(cfg), cfg.kv_lora_rank)
    return out(p, o_lat, cfg, x.dtype), lat

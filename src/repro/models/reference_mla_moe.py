"""Plain float32 reference of the latent-attention expert model
(Moonlight-16B-A3B's block, ``model_type: deepseek_v3``), for tests.

The whole forward pass in straightforward ``jax.numpy`` at matmul
precision "highest": no kernel, no cache, no batching tricks, nothing
imported from the program's layers. It takes the program's parameter tree
(any dtype; it computes in float32) and follows the published description:

- each layer: RMSNorm, latent attention, residual; RMSNorm, FFN, residual;
  then the final RMSNorm and the untied head;
- latent attention in its textbook form, not absorbed: q is one projection
  to ``nope + rope`` per head; ``wkv_a`` gives the latent (RMS-normed) and
  one rotary key shared by all heads; ``wkv_b`` expands the latent to each
  head's key (``nope``) and value (``v``); causal softmax at scale
  ``1/sqrt(nope + rope)``; split-half rotary embedding on the rotary parts;
- the leading ``first_k_dense_replace`` layers have a SwiGLU MLP; the rest
  route each token to its top ``num_experts_per_token`` of all
  ``num_experts`` by sigmoid score plus the correction bias, weigh the
  chosen experts by their scores, normalised and times
  ``routed_scaling_factor``, and add the shared experts. Of the routed
  experts only the held share, ``[first, first + held)`` (shard 0 holds
  the first), is computed, each over every token, weighted by zero where
  the token did not choose it: the chip's part of the layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (B, S, H, d), pos (S,): split-half rotation."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def attention(p: dict, x, cfg: ModelConfig):
    """Textbook latent attention of a whole sequence, x (B, S, D)."""
    b, s, _ = x.shape
    nope, rope, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.kv_lora_rank)
    h = cfg.num_heads
    pos = jnp.arange(s)
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    kv = x @ p["wkv_a"]
    c = _rms(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kv[..., None, r:], pos, cfg.rope_theta)       # (B,S,1,rope)
    up = jnp.einsum("bsr,rhe->bshe", c, p["wkv_b"])
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    v = up[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos,
                                              cfg.rope_theta)], -1)
    sc = jnp.einsum("bqhe,bkhe->bhqk", q, k) / math.sqrt(nope + rope)
    sc = jnp.where(pos[None, :] <= pos[:, None], sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(sc, -1), v)
    return jnp.einsum("bqhv,hvd->bqd", o, p["wo"])


def route(p: dict, x, cfg: ModelConfig):
    """Each token's chosen experts (T, k) over all ``num_experts`` and
    their weights; x (T, D)."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, ids = jax.lax.top_k(scores + p["router_bias"],
                           cfg.num_experts_per_token)
    w = jnp.take_along_axis(scores, ids, axis=1)
    return ids, w / w.sum(-1, keepdims=True) * cfg.routed_scaling_factor


def experts(p: dict, x, cfg: ModelConfig, first: int = 0):
    """The held share of the routed experts, from expert ``first`` on,
    plus the shared experts, for x (B, S, D)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    ids, w = route(p, xf, cfg)
    y = _swiglu(p["shared"], xf) if "shared" in p else jnp.zeros_like(xf)
    for e in range(p["w_gate"].shape[0]):
        mine = jnp.sum(jnp.where(ids == first + e, w, 0.0), -1)
        one = {n: p[n][e] for n in ("w_gate", "w_up", "w_down")}
        y = y + mine[:, None] * _swiglu(one, xf)
    return y.reshape(b, s, d)


def _layers(params: dict):
    """(layer params, is an expert layer) of every layer, in order."""
    out = []
    for name in ("dense_layers", "layers"):
        if name in params:
            group = params[name]
            n = jax.tree.leaves(group)[0].shape[0]
            out += [(jax.tree.map(lambda a, i=i: a[i], group),
                     "router" in group["ffn"]) for i in range(n)]
    return out


def logits(params: dict, tokens, cfg: ModelConfig):
    """Float32 logits (B, S, vocab) of the token sequences (B, S)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        x = p["embedding"][jnp.asarray(tokens)]
        for lp, routed in _layers(p):
            x = x + attention(lp["attn"], _rms(x, lp["ln1"], cfg.norm_eps),
                              cfg)
            m = _rms(x, lp["ln2"], cfg.norm_eps)
            x = x + (experts(lp["ffn"], m, cfg) if routed
                     else _swiglu(lp["ffn"], m))
        out = _rms(x, p["ln_f"], cfg.norm_eps) @ p["lm_head"]
        return np.asarray(out[..., :cfg.vocab_size])

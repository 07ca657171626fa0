"""Jamba-style hybrid: Mamba/attention interleave + alternating dense/MoE FFN.

The layer stack is periodic with period ``attn_every`` (8 for jamba): within
a period, sublayer i is an SSD mixer except the last, which is attention;
FFNs alternate dense/MoE per ``moe_every``. One period is unrolled in python
(heterogeneous params) and ``lax.scan`` runs over the ``num_layers /
attn_every`` identical periods — compact HLO with heterogeneous layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, moe, ssm, transformer

Array = jax.Array


def _period(cfg: ModelConfig) -> int:
    return cfg.attn_every


def _is_attn(cfg: ModelConfig, i: int) -> bool:
    return i == _period(cfg) - 1


def _is_moe(cfg: ModelConfig, i: int) -> bool:
    return cfg.moe_every > 0 and (i % cfg.moe_every == cfg.moe_every - 1)


def init_block(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    """One period of sublayers, keyed sub0..sub{p-1}."""
    p = _period(cfg)
    keys = jax.random.split(key, p)
    block = {}
    for i in range(p):
        ks = layers.split_keys(keys[i], ["mix", "ffn"])
        sub = {
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if _is_attn(cfg, i):
            sub["attn"] = transformer.init_attn(ks["mix"], cfg, dtype)
        else:
            sub["ssm"] = ssm.init_ssm(ks["mix"], cfg, dtype)
        if _is_moe(cfg, i):
            sub["moe"] = moe.init_moe(ks["ffn"], cfg, dtype)
        else:
            sub["mlp"] = layers.init_mlp(ks["ffn"], cfg.d_model, cfg.d_ff, dtype)
        block[f"sub{i}"] = sub
    return block


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    assert cfg.num_layers % _period(cfg) == 0
    nb = cfg.num_layers // _period(cfg)
    ks = layers.split_keys(key, ["emb", "head", "blocks"])
    bkeys = jax.random.split(ks["blocks"], nb)
    stacked = jax.vmap(lambda k: init_block(k, cfg, dtype))(bkeys)
    return {
        "embedding": layers.init_embedding(ks["emb"], cfg.padded_vocab,
                                           cfg.d_model, dtype),
        "blocks": stacked,
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        "lm_head": layers.dense_init(ks["head"], (cfg.d_model, cfg.padded_vocab),
                                     dtype=dtype),
    }


def _sub_ffn(sub: dict, x: Array, cfg: ModelConfig,
             token_mask: Array | None = None, serving: bool = False):
    """(y, aux). ``serving``: expert sublayers run dropless (see
    ``moe.moe_dispatch``), so a row's output never depends on its
    batch-mates."""
    if "moe" in sub:
        return moe.moe_dispatch(sub["moe"], x, cfg, token_mask,
                                dropless=serving)[:2]
    return layers.mlp(sub["mlp"], x), jnp.zeros((), jnp.float32)


def forward(params: dict, tokens: Array, cfg: ModelConfig, *,
            remat: str = "full", return_cache: bool = False):
    x = layers.embed(params["embedding"], tokens)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    p = _period(cfg)

    def body(carry, bp):
        x, aux = carry
        kv_out = None
        ssm_out = []
        for i in range(p):
            sub = bp[f"sub{i}"]
            h = layers.rmsnorm(x, sub["ln1"], cfg.norm_eps)
            if _is_attn(cfg, i):
                out, kv_out = transformer.attention_block(sub["attn"], h, cfg,
                                                          positions)
            else:
                out, st = ssm.ssd_forward(sub["ssm"], h, cfg)
                ssm_out.append(st)
            x = x + out
            h2 = layers.rmsnorm(x, sub["ln2"], cfg.norm_eps)
            f, a = _sub_ffn(sub, h2, cfg)
            x = x + f
            aux = aux + a
        ys = None
        if return_cache:
            states = jax.tree.map(lambda *a: jnp.stack(a), *ssm_out)
            ys = (kv_out, states)
        return (x, aux), ys

    if remat != "none":
        body = jax.checkpoint(body)
    (x, aux), ys = layers.scan(body, (x, jnp.zeros((), jnp.float32)),
                                params["blocks"])
    x = layers.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = layers.unembed(x, params["lm_head"], transpose=False)
    if return_cache:
        (k, v), states = ys
        return logits, aux, {"k": k, "v": v, "ssm": states}
    return logits, aux


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> dict:
    nb = cfg.num_layers // _period(cfg)
    n_ssm = _period(cfg) - 1
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    one = ssm.init_ssm_state(cfg, batch, dtype)
    states = jax.tree.map(
        lambda a: jnp.zeros((nb, n_ssm) + a.shape, a.dtype), one)
    return {
        "k": jnp.zeros((nb, batch, max_seq, kvh, hd), dtype),
        "v": jnp.zeros((nb, batch, max_seq, kvh, hd), dtype),
        "ssm": states,
    }


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     batch: int, dtype=jnp.bfloat16) -> dict:
    """Paged hybrid cache: the attention sublayers' KV moves into a shared
    page pool (one pool row per period-block, addressed by the engine's
    block tables); the SSD sublayers' recurrent state is O(1) per slot and
    stays slot-resident — there is nothing to page."""
    nb = cfg.num_layers // _period(cfg)
    n_ssm = _period(cfg) - 1
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    one = ssm.init_ssm_state(cfg, batch, dtype)
    states = jax.tree.map(
        lambda a: jnp.zeros((nb, n_ssm) + a.shape, a.dtype), one)
    return {
        "k_pages": jnp.zeros((nb, num_pages, kvh, hd, page_size), dtype),
        "v_pages": jnp.zeros((nb, num_pages, kvh, hd, page_size), dtype),
        "ssm": states,
    }


def prefill(params: dict, tokens: Array, cfg: ModelConfig, max_seq: int):
    logits, _, cache = forward(params, tokens, cfg, remat="none",
                               return_cache=True)
    s = tokens.shape[1]
    if max_seq > s:
        pad = [(0, 0), (0, 0), (0, max_seq - s), (0, 0), (0, 0)]
        cache["k"] = jnp.pad(cache["k"].astype(jnp.bfloat16), pad)
        cache["v"] = jnp.pad(cache["v"].astype(jnp.bfloat16), pad)
    return logits, cache


def decode_step(params: dict, cache: dict, tokens: Array, lengths: Array,
                cfg: ModelConfig, active: Array | None = None):
    """``active``: optional (B,) bool mask — inactive rows keep both their
    KV rows (length-masked scatter) and their SSM state (where-mask)."""
    x = layers.embed(params["embedding"], tokens)
    pcount = _period(cfg)

    def body(x, inp):
        bp, kc, vc, states = inp
        new_states = []
        si = 0
        for i in range(pcount):
            sub = bp[f"sub{i}"]
            h = layers.rmsnorm(x, sub["ln1"], cfg.norm_eps)
            if _is_attn(cfg, i):
                out, (kc, vc) = transformer.attention_decode_block(
                    sub["attn"], h, cfg, kc, vc, lengths, active=active)
            else:
                st_i = jax.tree.map(lambda a: a[si], states)
                out, st_i = ssm.ssm_decode_step(sub["ssm"], h, st_i, cfg,
                                                active=active)
                new_states.append(st_i)
                si += 1
            x = x + out
            h2 = layers.rmsnorm(x, sub["ln2"], cfg.norm_eps)
            f, _ = _sub_ffn(sub, h2, cfg, token_mask=active, serving=True)
            x = x + f
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *new_states)
        return x, (kc, vc, stacked)

    x, (k, v, states) = layers.scan(
        body, x, (params["blocks"], cache["k"], cache["v"], cache["ssm"]))
    x = layers.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = layers.unembed(x, params["lm_head"], transpose=False)
    return logits[:, 0], {"k": k, "v": v, "ssm": states}


def decode_step_paged(params: dict, cache: dict, tokens: Array,
                      lengths: Array, block_tables: Array, cfg: ModelConfig,
                      active: Array | None = None):
    """Paged decode across the SSD/attention interleave: attention KV goes
    through the page pool + block tables; SSM state stays slot-resident
    (same where-mask isolation as :func:`decode_step`)."""
    x = layers.embed(params["embedding"], tokens)
    pcount = _period(cfg)

    def body(x, inp):
        bp, kp, vp, states = inp
        new_states = []
        si = 0
        for i in range(pcount):
            sub = bp[f"sub{i}"]
            h = layers.rmsnorm(x, sub["ln1"], cfg.norm_eps)
            if _is_attn(cfg, i):
                out, (kp, vp) = transformer.attention_decode_block_paged(
                    sub["attn"], h, cfg, kp, vp, block_tables, lengths,
                    active=active)
            else:
                st_i = jax.tree.map(lambda a: a[si], states)
                out, st_i = ssm.ssm_decode_step(sub["ssm"], h, st_i, cfg,
                                                active=active)
                new_states.append(st_i)
                si += 1
            x = x + out
            h2 = layers.rmsnorm(x, sub["ln2"], cfg.norm_eps)
            f, _ = _sub_ffn(sub, h2, cfg, token_mask=active, serving=True)
            x = x + f
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *new_states)
        return x, (kp, vp, stacked)

    x, (k, v, states) = layers.scan(
        body, x, (params["blocks"], cache["k_pages"], cache["v_pages"],
                  cache["ssm"]))
    x = layers.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = layers.unembed(x, params["lm_head"], transpose=False)
    return logits[:, 0], {"k_pages": k, "v_pages": v, "ssm": states}


def prefill_chunk_paged(params: dict, cache: dict, tokens: Array,
                        start_len: Array, block_tables: Array,
                        cfg: ModelConfig, active: Array | None = None,
                        valid: Array | None = None):
    """Paged batched chunked prefill; see :func:`prefill_chunk`."""
    x = layers.embed(params["embedding"], tokens)
    pcount = _period(cfg)
    live = transformer._live(tokens, active, valid)

    def body(x, inp):
        bp, kp, vp, states = inp
        new_states = []
        si = 0
        for i in range(pcount):
            sub = bp[f"sub{i}"]
            h = layers.rmsnorm(x, sub["ln1"], cfg.norm_eps)
            if _is_attn(cfg, i):
                out, (kp, vp) = \
                    transformer.attention_prefill_chunk_block_paged(
                        sub["attn"], h, cfg, kp, vp, block_tables, start_len,
                        active=active, valid=valid)
            else:
                st_i = jax.tree.map(lambda a: a[si], states)
                out, new_st = ssm.ssd_forward(sub["ssm"], h, cfg,
                                              init_state=st_i,
                                              token_valid=valid)
                if active is not None:
                    new_st = ssm.mask_state(new_st, st_i, active)
                new_states.append(new_st)
                si += 1
            x = x + out
            h2 = layers.rmsnorm(x, sub["ln2"], cfg.norm_eps)
            f, _ = _sub_ffn(sub, h2, cfg, token_mask=live,
                            serving=True)
            x = x + f
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *new_states)
        return x, (kp, vp, stacked)

    x, (k, v, states) = layers.scan(
        body, x, (params["blocks"], cache["k_pages"], cache["v_pages"],
                  cache["ssm"]))
    x = layers.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = layers.unembed(x, params["lm_head"], transpose=False)
    return logits, {"k_pages": k, "v_pages": v, "ssm": states}


def prefill_chunk(params: dict, cache: dict, tokens: Array, start_len: Array,
                  cfg: ModelConfig, active: Array | None = None,
                  valid: Array | None = None):
    """Batched chunked prefill across the SSD/attention interleave.

    tokens: (B,C); start_len: (B,). Attention sublayers write the chunk's
    k/v at per-row offsets (length-masked scatter) and attend over the
    padded cache; SSD sublayers run one chunked-SSD pass from the cached
    recurrent state. One jitted dispatch per chunk for the whole stack.
    ``valid``: optional (B,) real-token count per row (pads at the tail,
    multi-slot batched prefill) — pad tokens write no KV and get dt=0 in
    the SSD sublayers; their logits are garbage the engine discards.
    """
    x = layers.embed(params["embedding"], tokens)
    pcount = _period(cfg)
    live = transformer._live(tokens, active, valid)

    def body(x, inp):
        bp, kc, vc, states = inp
        new_states = []
        si = 0
        for i in range(pcount):
            sub = bp[f"sub{i}"]
            h = layers.rmsnorm(x, sub["ln1"], cfg.norm_eps)
            if _is_attn(cfg, i):
                out, (kc, vc) = transformer.attention_prefill_chunk_block(
                    sub["attn"], h, cfg, kc, vc, start_len, active=active,
                    valid=valid)
            else:
                st_i = jax.tree.map(lambda a: a[si], states)
                out, new_st = ssm.ssd_forward(sub["ssm"], h, cfg,
                                              init_state=st_i,
                                              token_valid=valid)
                if active is not None:
                    new_st = ssm.mask_state(new_st, st_i, active)
                new_states.append(new_st)
                si += 1
            x = x + out
            h2 = layers.rmsnorm(x, sub["ln2"], cfg.norm_eps)
            f, _ = _sub_ffn(sub, h2, cfg, token_mask=live,
                            serving=True)
            x = x + f
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *new_states)
        return x, (kc, vc, stacked)

    x, (k, v, states) = layers.scan(
        body, x, (params["blocks"], cache["k"], cache["v"], cache["ssm"]))
    x = layers.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = layers.unembed(x, params["lm_head"], transpose=False)
    return logits, {"k": k, "v": v, "ssm": states}

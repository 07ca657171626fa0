"""GShard-style top-k MoE with capacity-bounded scatter dispatch.

Dispatch is expressed as k scatter/gather pairs between the token-sharded
activation layout (tokens on the "data"/"pod" axes) and the expert-sharded
buffer layout (experts on the "model" axis). Under pjit this crossing lowers
to all-to-all/collective-permute traffic — exactly the EP communication the
roofline table measures. Capacity is static (derived from shapes), so the
whole layer is shape-stable inside ``lax.scan`` over layers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers

Array = jax.Array


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    ks = layers.split_keys(key, ["router", "gate", "up", "down", "shared"])
    params = {
        "router": layers.dense_init(ks["router"], (d, e), dtype=jnp.float32),
        "w_gate": layers.dense_init(ks["gate"], (e, d, f), dtype=dtype),
        "w_up": layers.dense_init(ks["up"], (e, d, f), dtype=dtype),
        "w_down": layers.dense_init(ks["down"], (e, f, d), dtype=dtype),
    }
    if cfg.num_shared_experts:
        params["shared"] = layers.init_mlp(
            ks["shared"], d, f * cfg.num_shared_experts, dtype=dtype)
    return params


def capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = math.ceil(cfg.num_experts_per_token * num_tokens *
                  cfg.capacity_factor / cfg.num_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for clean tiling


def moe_ffn(params: dict, x: Array, cfg: ModelConfig,
            token_mask: Array | None = None) -> tuple[Array, Array]:
    """x: (B, S, D) -> (y, aux_loss). Aux = load-balance + router z-loss.

    ``token_mask``: optional (B,) bool row mask (the serving engine's
    active-slot mask). Masked-out rows neither occupy expert capacity nor
    receive output — without this, the garbage tokens of idle/mid-prefill
    slots in a mask-isolated decode batch would compete with live slots for
    capacity and could evict their tokens (cross-slot interference).
    """
    b, s, d = x.shape
    t = b * s
    k = cfg.num_experts_per_token
    e = cfg.num_experts
    c = capacity(cfg, t)

    xf = x.reshape(t, d)
    router_logits = (xf.astype(jnp.float32) @ params["router"])  # (T, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, k)                         # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # position of each token inside its expert's capacity buffer
    onehot = jnp.sum(jax.nn.one_hot(eids, e, dtype=jnp.int32), axis=1)  # (T,E) 0/1
    if token_mask is not None:
        tok_live = jnp.repeat(token_mask, s)                            # (T,)
        onehot = onehot * tok_live[:, None].astype(onehot.dtype)
    pos_all = jnp.cumsum(onehot, axis=0) * onehot - 1                   # (T,E)
    pos = jnp.take_along_axis(pos_all, eids, axis=1)                    # (T,k)
    keep = (pos >= 0) & (pos < c)
    if token_mask is not None:
        keep = keep & tok_live[:, None]
    pos_c = jnp.clip(pos, 0, c - 1)

    # ---- dispatch: k scatters token->expert-buffer (data->model crossing)
    xe = jnp.zeros((e, c, d), x.dtype)
    for j in range(k):
        contrib = jnp.where(keep[:, j, None], xf, 0)
        xe = xe.at[eids[:, j], pos_c[:, j]].add(contrib)

    # ---- expert FFN (batched over experts; E is model-sharded)
    g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    # ---- combine: k gathers expert-buffer->token
    y = jnp.zeros((t, d), x.dtype)
    for j in range(k):
        yj = ye[eids[:, j], pos_c[:, j]]
        w = (gates[:, j] * keep[:, j]).astype(x.dtype)
        y = y + yj * w[:, None]

    if "shared" in params:
        y = y + layers.mlp(params["shared"], xf)

    # load-balance aux (Switch): E * sum_e f_e * p_e ; + router z-loss
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(eids, e, dtype=jnp.float32), axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    lb = e * jnp.sum(f_e * p_e)
    z = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    aux = lb + 1e-3 * z
    return y.reshape(b, s, d), aux


# --------------------------------------------------------------------------
# shard_map expert-parallel dispatch (hillclimb variant, hints.moe_impl)
# --------------------------------------------------------------------------
# Routing is computed redundantly on every model shard (tokens are
# model-replicated at the FFN input under TP); each model shard gathers ONLY
# the tokens routed to ITS local experts — zero dispatch communication — and
# a single psum over "model" combines expert outputs. Replaces the baseline's
# data->model scatters, which XLA lowers to per-layer all-gathers of the
# whole (E, C, D) buffer (measured: 37 TB/chip for kimi prefill_32k).

def _ambient_mesh_axes():
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def moe_ffn_shardmap(params: dict, x: Array, cfg: ModelConfig):
    """Drop-in for moe_ffn under a ('data','model') (+'pod') mesh context."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = _ambient_mesh_axes()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_ffn(params, x, cfg)
    mp_size = mesh.shape["model"]
    if cfg.num_experts % mp_size:
        return moe_ffn(params, x, cfg)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b, s, d = x.shape
    k = cfg.num_experts_per_token
    e = cfg.num_experts
    e_l = e // mp_size
    dpn = 1
    for a in dp:
        dpn *= mesh.shape[a]
    bspec = (dp if len(dp) > 1 else dp[0]) if dp and b % dpn == 0 else None
    t_l = (b // dpn if bspec else b) * s
    c_l = capacity(cfg, t_l * mp_size) // mp_size  # same global capacity
    c_l = max(8, ((c_l + 7) // 8) * 8)

    def body(router, wg, wu, wd, x_l):
        # x_l: (B_l, S, D) — model-replicated
        m_idx = jax.lax.axis_index("model")
        xf = x_l.reshape(-1, d)
        logits = xf.astype(jnp.float32) @ router          # (T_l, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eids = jax.lax.top_k(probs, k)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

        e0 = m_idx * e_l
        onehot = jnp.sum(jax.nn.one_hot(eids - e0, e_l, dtype=jnp.int32),
                         axis=1)                           # (T_l, E_l); OOR->0
        pos_all = jnp.cumsum(onehot, axis=0) * onehot - 1  # (T_l, E_l)

        xe = jnp.zeros((e_l, c_l, d), x_l.dtype)
        for j in range(k):
            e_rel = eids[:, j] - e0
            valid = (e_rel >= 0) & (e_rel < e_l)
            e_c = jnp.clip(e_rel, 0, e_l - 1)
            pj = jnp.take_along_axis(pos_all, e_c[:, None], axis=1)[:, 0]
            keep = valid & (pj >= 0) & (pj < c_l)
            contrib = jnp.where(keep[:, None], xf, 0)
            xe = xe.at[e_c, jnp.clip(pj, 0, c_l - 1)].add(contrib)

        g = jnp.einsum("ecd,edf->ecf", xe, wg)
        u = jnp.einsum("ecd,edf->ecf", xe, wu)
        h = jax.nn.silu(g) * u
        ye = jnp.einsum("ecf,efd->ecd", h, wd)

        y = jnp.zeros_like(xf)
        for j in range(k):
            e_rel = eids[:, j] - e0
            valid = (e_rel >= 0) & (e_rel < e_l)
            e_c = jnp.clip(e_rel, 0, e_l - 1)
            pj = jnp.take_along_axis(pos_all, e_c[:, None], axis=1)[:, 0]
            keep = valid & (pj >= 0) & (pj < c_l)
            yj = ye[e_c, jnp.clip(pj, 0, c_l - 1)]
            w = (gates[:, j] * keep).astype(x_l.dtype)
            y = y + yj * w[:, None]
        y = jax.lax.psum(y, "model")

        # aux: identical on every shard (routing replicated). Scatter-add
        # instead of a (T,k,E) one-hot (805 MB/layer at kimi prefill scale).
        counts = jnp.zeros((e,), jnp.float32).at[eids.reshape(-1)].add(1.0)
        f_e = counts / eids.shape[0]
        p_e = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(f_e * p_e) + 1e-3 * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2)
        aux = jax.lax.pmean(aux, "model")
        if dp:
            for a in dp:
                aux = jax.lax.pmean(aux, a)
        return y.reshape(x_l.shape), aux

    y, aux = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P(bspec, None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"], x)

    if jax.sharding.AxisType.Explicit in mesh.axis_types:
        # explicit mesh axes (jax.make_mesh's default) put the out_specs'
        # batch sharding into y's type; hand back the input's type so the
        # layer scan's residual carry keeps one type across layers
        y = jax.sharding.reshard(y, jax.typeof(x).sharding.spec)
    if "shared" in params:
        y = y + layers.mlp(params["shared"], x)
    return y, aux


def moe_dispatch(params: dict, x: Array, cfg: ModelConfig,
                 token_mask: Array | None = None):
    """Entry point honoring the hints.moe_impl knob.

    ``token_mask`` (serving active-slot mask) forces the scatter path — the
    shard_map variant is a train/prefill optimization and never sees decode
    batches with dead rows (autotune table: shardmap loses on decode)."""
    from repro.distributed import hints
    if token_mask is None and hints.get("moe_impl") == "shardmap":
        return moe_ffn_shardmap(params, x, cfg)
    return moe_ffn(params, x, cfg, token_mask)

"""Two expert layers: a held-share dropless layer (serving, and every
sigmoid-routed model) and GShard-style top-k MoE with capacity-bounded
scatter dispatch (training of softmax-routed models).

The held-share layer (:func:`moe_held`) routes each token over all
``num_experts`` at the router's published width, computes only the picks
that land on the experts this device holds (``[first, first +
held_experts)``; shard 0 of a deployment holds the first) with grouped
matmuls over them, and
drops no token: a row's output never depends on its batch-mates. On one
chip of an expert-parallel deployment it runs without its exchange; the
shares of all chips, with the shared experts counted once, add up to the
whole layer.

The capacity-bounded layer:

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
    held = cfg.held_experts
    ks = layers.split_keys(key, ["router", "gate", "up", "down", "shared"])
    params = {
        "router": layers.dense_init(ks["router"], (d, e), dtype=jnp.float32),
        "w_gate": layers.dense_init(ks["gate"], (held, d, f), dtype=dtype),
        "w_up": layers.dense_init(ks["up"], (held, d, f), dtype=dtype),
        "w_down": layers.dense_init(ks["down"], (held, f, d), dtype=dtype),
    }
    if cfg.scoring_func == "sigmoid":
        # the correction bias: it moves which experts are chosen, never the
        # weight a chosen expert's output is given
        params["router_bias"] = jnp.zeros((e,), jnp.float32)
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
                 token_mask: Array | None = None, *, dropless: bool = False):
    """Entry point: ``(y, aux, counts)``, ``counts`` as :func:`moe_held`
    returns them (zeros from the capacity path).

    Sigmoid-routed models, and every model the engine serves
    (``dropless``), take the held-share dropless layer. Otherwise the
    capacity-bounded layer, honoring the hints.moe_impl knob (the
    shard_map form is of the capacity-bounded layer only)."""
    if dropless or cfg.scoring_func == "sigmoid":
        return moe_held(params, x, cfg, token_mask)
    from repro.distributed import hints
    if token_mask is None and hints.get("moe_impl") == "shardmap":
        y, aux = moe_ffn_shardmap(params, x, cfg)
    else:
        y, aux = moe_ffn(params, x, cfg, token_mask)
    return y, aux, jnp.zeros((3,), jnp.int32)


# --------------------------------------------------------------------------
# held-share dropless layer
# --------------------------------------------------------------------------

def route(params: dict, xf: Array, cfg: ModelConfig):
    """Each token's ``num_experts_per_token`` experts over all
    ``num_experts`` and the weight of each, ``(T, k)`` ids and float32
    weights, and the float32 scores. Sigmoid scoring chooses by score plus
    the correction bias and weighs by the chosen scores, normalised and
    scaled by ``routed_scaling_factor``; softmax scoring chooses and weighs
    by the normalised top-k probabilities.

    The router's product is float32 at full precision, as the published
    DeepSeek-V3 gate computes it (a TPU's default float32 product rounds
    both operands to bfloat16, which flips near-tied picks)."""
    logits = jnp.matmul(xf.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    k = cfg.num_experts_per_token
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, eids = jax.lax.top_k(scores + params["router_bias"], k)
        w = jnp.take_along_axis(scores, eids, axis=1)
        w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scaling_factor
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        w, eids = jax.lax.top_k(scores, k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return eids, w, scores


def moe_held(params: dict, x: Array, cfg: ModelConfig,
             token_mask: Array | None = None, first: int = 0):
    """x: (B, S, D) -> (y, aux, counts), dropless, over the held experts.

    Every live token is routed over all ``num_experts``; its picks that
    land in ``[first, first + held)`` are sorted by expert and computed by
    grouped matmuls (``lax.ragged_dot``) over the held experts' weights,
    with no capacity: nothing is dropped and a token's output does not
    depend on the other tokens. Then the shared experts are added, for every token.

    ``token_mask``: optional (B,) bool row mask, or (B, S) token mask;
    tokens outside it are routed nowhere (their outputs are garbage the
    caller ignores) and not counted.
    ``counts``: int32 ``(picks, held picks, the busiest held expert's
    picks)`` of the live tokens. ``aux``: the sequence-wise balance loss of
    DeepSeek-V3 over the batch (1 when balanced)."""
    b, s, d = x.shape
    t = b * s
    k = cfg.num_experts_per_token
    e = cfg.num_experts
    held = params["w_gate"].shape[0]
    xf = x.reshape(t, d)
    eids, w, scores = route(params, xf, cfg)
    live = (jnp.ones((t,), bool) if token_mask is None else
            jnp.broadcast_to(token_mask.reshape(b, -1), (b, s)).reshape(t))
    rel = eids - first
    mine = (rel >= 0) & (rel < held) & live[:, None]              # (T, k)
    group = jnp.where(mine, rel, held).reshape(-1)                 # (T*k,)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    xs = xf[order // k]                                            # (T*k, D)

    def experts(xs, wg, wu, wd, sizes):
        g = jax.lax.ragged_dot(xs, wg, sizes)
        u = jax.lax.ragged_dot(xs, wu, sizes)
        return jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(xs.dtype), wd,
                                  sizes)

    mesh = _ambient_mesh_axes()
    if mesh is not None and jax.sharding.AxisType.Explicit in mesh.axis_types:
        # ragged_dot has no sharding rule under explicit mesh axes
        from jax.sharding import PartitionSpec as P
        experts = jax.sharding.auto_axes(experts, out_sharding=P())
    ys = experts(xs, params["w_gate"], params["w_up"], params["w_down"],
                 sizes)
    # rows past the held picks are in no group: zero them, then put each
    # pick back in its token's place
    ys = jnp.where((jnp.arange(t * k) < jnp.sum(sizes))[:, None], ys, 0)
    yk = jnp.zeros_like(ys).at[order].set(ys).reshape(t, k, d)
    wk = jnp.where(mine, w, 0.0).astype(x.dtype)
    y = jnp.einsum("tkd,tk->td", yk, wk)
    if "shared" in params:
        y = y + layers.mlp(params["shared"], xf)
    counts = jnp.stack([k * jnp.sum(live), jnp.sum(mine),
                        jnp.max(sizes)]).astype(jnp.int32)
    # balance: E/k * sum_e (share of picks) * (mean normalised score)
    picks = jnp.zeros((e,), jnp.float32).at[eids.reshape(-1)].add(1.0)
    p_e = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=0)
    aux = e / k * jnp.sum(picks / t * p_e)
    return y.reshape(b, s, d), aux, counts


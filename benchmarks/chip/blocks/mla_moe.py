"""The latent-attention expert block (DeepSeek-V3's, as Moonlight-16B-A3B
publishes it): what the benchmark needs to judge and count a configuration
that states ``"block": "mla_moe"``.

The block (see the configuration's ``assumed``): RMSNorm before attention
and before the FFN; latent attention with no q LoRA (q one projection to
``nope + rope`` per head; ``wkv_a`` to an RMS-normed latent and one rotary
key shared by all heads; ``wkv_b`` expanding the latent to each head's key
and value), rotary embedding in split halves; the leading
``first_k_dense_replace`` layers (``dense_layers/``) with a SwiGLU MLP, the
rest (``layers/``) with sigmoid-routed experts: top ``num_experts_per_token``
of all ``num_experts`` by score plus a correction bias, the chosen scores
normalised and scaled by ``routed_scaling_factor``, of which this chip
computes its held share (shard 0: the first ``experts_held``), plus the
shared experts; untied head.

Its float32 reference is the benchmark's own copy of the layer, in the
textbook form that does not absorb ``wkv_b`` (each head's key and value
computed from the latent), run by ``chipbench.reference``'s driver; every
held expert runs over every token, weighted by zero where the token did not
choose it. Its work is counted in the absorbed form the program runs: each
query head attends the latent rows directly, ``kv_lora_rank +
qk_rope_head_dim`` wide, and the values are their first ``kv_lora_rank``
columns. Its step programs are told apart in a trace by the paged latent
attention kernel each holds.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from chipbench import reference
from chipbench.reference import Q_BLOCK, mm, rmsnorm, rope

_ATTN = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
_MLP = ("w_gate", "w_up", "w_down")
#: the parameter tree the reference computes; anything else is refused
LEAVES = ({"embedding", "lm_head", "ln_f"}
          | {f"{g}/{n}" for g in ("dense_layers", "layers")
             for n in ("ln1", "ln2")
             + tuple(f"attn/{a}" for a in _ATTN)}
          | {f"dense_layers/ffn/{n}" for n in _MLP}
          | {f"layers/ffn/{n}" for n in _MLP + ("router", "router_bias")}
          | {f"layers/ffn/shared/{n}" for n in _MLP})
#: groups of the tree whose leaves are stacked over layers
STACKED = ("dense_layers/", "layers/")
#: kernel name (the prefix of its operation's name) -> the step it marks
KERNELS = {"paged_mla_prefill_attention": "prefill",
           "paged_mla_decode_attention": "decode"}


def check_tree(specs: dict) -> None:
    if set(specs) != LEAVES:
        raise ValueError(
            "the program's parameters are not the latent-attention expert "
            "block the reference computes: extra "
            f"{sorted(set(specs) - LEAVES)}, missing "
            f"{sorted(LEAVES - set(specs))}")


# -------------------------------------------------------------- reference

@dataclasses.dataclass(frozen=True)
class Routing:
    """What the reference's expert layers need beyond the tree."""
    top_k: int
    scale: float
    dense: int           # leading dense layers


def _attention(w, a, blk, control):
    """Textbook latent attention of a (B, S, D) normed sequence."""
    b, s, dm = a.shape
    wq, wkv_a, wkv_b, wo = w("attn/wq"), w("attn/wkv_a"), w("attn/wkv_b"), \
        w("attn/wo")
    h, vd = wo.shape[0], wo.shape[1]
    r = wkv_b.shape[0]
    nope = wkv_b.shape[2] - vd
    rp = wq.shape[2] - nope
    pos = jnp.arange(s)
    q = mm(a, wq.reshape(dm, -1), control).reshape(b, s, h, nope + rp)
    kv = mm(a, wkv_a, control)
    c = rmsnorm(kv[..., :r], w("attn/kv_norm"), blk.eps)
    k_pe = rope(kv[..., None, r:], pos, blk.theta)
    up = mm(c, wkv_b.reshape(r, -1), control).reshape(b, s, h, nope + vd)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rp))], -1)
    v = up[..., nope:]
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos,
                                             blk.theta)], -1)
    outs = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                        precision=reference.HIGHEST) / math.sqrt(nope + rp)
        qpos = lo + jnp.arange(qb.shape[1])
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                               precision=reference.HIGHEST))
    o = jnp.concatenate(outs, axis=1)
    return mm(o.reshape(b, s, h * vd), wo.reshape(h * vd, dm), control)


def _swiglu(m, gate, up, down, control):
    return mm(jax.nn.silu(mm(m, gate, control)) * mm(m, up, control), down,
              control)


def _make_layer(group: str, experts: bool):
    """The jitted reference layer of a group: layer ``n`` of its stack."""
    @functools.partial(jax.jit, static_argnames=("blk", "control", "route"))
    def layer_fn(x, key, n, *, blk, control, route):
        def w(name):
            return blk.draw(key, f"{group}/{name}", n)

        x = x + _attention(w, rmsnorm(x, w("ln1"), blk.eps), blk, control)
        m = rmsnorm(x, w("ln2"), blk.eps)
        if not experts:
            return x + _swiglu(m, w("ffn/w_gate"), w("ffn/w_up"),
                               w("ffn/w_down"), control)
        scores = jax.nn.sigmoid(mm(m, w("ffn/router"), control))
        _, ids = jax.lax.top_k(scores + w("ffn/router_bias"), route.top_k)
        gate = jnp.take_along_axis(scores, ids, axis=-1)
        gate = gate / gate.sum(-1, keepdims=True) * route.scale
        y = _swiglu(m, w("ffn/shared/w_gate"), w("ffn/shared/w_up"),
                    w("ffn/shared/w_down"), control)
        wg, wu, wd = w("ffn/w_gate"), w("ffn/w_up"), w("ffn/w_down")
        for e in range(wg.shape[0]):
            mine = jnp.sum(jnp.where(ids == e, gate, 0.0), -1)
            y = y + mine[..., None] * _swiglu(m, wg[e], wu[e], wd[e],
                                              control)
        return x + y
    return layer_fn


_DENSE_LAYER = _make_layer("dense_layers", experts=False)
_EXPERT_LAYER = _make_layer("layers", experts=True)


def logits_at(cfg: dict, specs: dict, seed: int, sequences: list,
              control: bool = False) -> list:
    """Float32 logits ``(K, vocab)`` of each sequence at its served
    positions (see ``reference.logits_at``); with ``control``, every
    projection (the router's among them) in float8."""
    check_tree(specs)
    dense = specs["dense_layers/ln1"][0][0]
    layers = dense + specs["layers/ln1"][0][0]
    blk = reference.Block.of(cfg, specs, layers=layers)
    route = Routing(int(cfg["num_experts_per_token"]),
                    float(cfg["routed_scaling_factor"]), int(dense))

    def layer(x, key, n, *, blk, control):
        n = int(n)
        if n < route.dense:
            return _DENSE_LAYER(x, key, jnp.int32(n), blk=blk,
                                control=control, route=route)
        return _EXPERT_LAYER(x, key, jnp.int32(n - route.dense), blk=blk,
                             control=control, route=route)

    return reference.logits_at(blk, layer, seed, sequences, control)


# ------------------------------------------------------------------- work

@dataclasses.dataclass(frozen=True)
class Widths:
    """The widths of the block, as the configuration states them."""
    layers: int
    dense: int               # leading dense layers
    d_model: int
    heads: int
    nope: int
    rope: int
    rank: int                # kv_lora_rank
    v: int
    d_ff: int
    experts: int             # the router's width
    held: int                # experts held here
    top_k: int
    expert_ff: int
    shared: int
    vocab: int
    kv_bytes: int = 2        # bf16 latent rows
    q_bytes: int = 4         # float32 absorbed queries and weighted latents

    @classmethod
    def of(cls, cfg: dict) -> "Widths":
        return cls(layers=cfg["num_layers"],
                   dense=cfg["first_k_dense_replace"],
                   d_model=cfg["d_model"], heads=cfg["num_heads"],
                   nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                   rank=cfg["kv_lora_rank"], v=cfg["v_head_dim"],
                   d_ff=cfg["d_ff"], experts=cfg["num_experts"],
                   held=cfg.get("experts_held") or cfg["num_experts"],
                   top_k=cfg["num_experts_per_token"],
                   expert_ff=cfg["moe_d_ff"],
                   shared=cfg["num_shared_experts"],
                   vocab=cfg["vocab_size"])

    @property
    def width(self) -> int:
        """One latent row: the latent and the shared rotary key."""
        return self.rank + self.rope

    @property
    def attn_params(self) -> int:
        """Multiply-adds a token makes in a layer's latent attention outside
        the kernel, absorbed: q, the latent, q_nope through the key half of
        ``wkv_b``, the weighted latent through its value half, and ``wo``."""
        d, h = self.d_model, self.heads
        return (d * h * (self.nope + self.rope) + d * self.width
                + h * self.nope * self.rank + h * self.rank * self.v
                + h * self.v * d)

    @property
    def expert_layer_params(self) -> int:
        """Multiply-adds a token makes in an expert layer's FFN: the router,
        the shared experts, and the routed experts at the expected held
        share, ``top_k * held / experts`` expert FFNs."""
        per = 3 * self.d_model * self.expert_ff
        return (self.d_model * self.experts + self.shared * per
                + per * self.top_k * self.held / self.experts)


def attention(m: Widths, keys: float, rows: int, queries: int
              ) -> tuple[float, float]:
    """(FLOPs, bytes) of one paged latent-attention call (one layer):
    ``queries`` tokens of ``heads`` heads over ``keys`` (query, key) pairs
    in all; each head's scores over the whole row width and its weighted
    latent over the first ``rank`` columns, at 2 FLOPs a multiply-add.
    Bytes: ``rows`` latent rows read once, the queries read and the
    weighted latents written."""
    flops = 2.0 * m.heads * (m.width + m.rank) * keys
    byts = (m.kv_bytes * m.width * rows
            + m.q_bytes * m.heads * (m.width + m.rank) * queries)
    return flops, float(byts)


def decode_attention(m: Widths, lengths) -> tuple[float, float]:
    """A decode call over live rows that attend ``lengths`` keys each (the
    new token included)."""
    keys = sum(int(n) for n in lengths)
    return attention(m, keys, keys, len(lengths))


def prefill_attention(m: Widths, rows) -> tuple[float, float]:
    """A prefill call; ``rows``: (start, c) of each live row: c new
    queries at positions ``start .. start+c-1``, each attending causally to
    every row up to its own; ``start + c`` rows read once."""
    keys = sum(int(c) * int(s) + int(c) * (int(c) + 1) // 2
               for s, c in rows)
    read = sum(int(s) + int(c) for s, c in rows)
    return attention(m, keys, read, sum(int(c) for _, c in rows))


def step_flops(m: Widths, *, prefill_rows=(), decode_lengths=()) -> float:
    """Useful model FLOPs of the window's dispatches, all layers: the
    projections and FFNs of every live token, attention at its true length
    (absorbed), and the unembedding of decode tokens only."""
    tokens = sum(int(c) for _, c in prefill_rows) + len(decode_lengths)
    experts = m.layers - m.dense
    per_token = (m.layers * m.attn_params + m.dense * 3 * m.d_model * m.d_ff
                 + experts * m.expert_layer_params)
    fl = 2.0 * per_token * tokens
    fl += m.layers * prefill_attention(m, prefill_rows)[0]
    fl += m.layers * decode_attention(m, decode_lengths)[0]
    fl += 2.0 * m.d_model * m.vocab * len(decode_lengths)
    return fl


def work(cfg: dict, prefill_rows, decode_lengths) -> tuple:
    """Useful work of the window's dispatches: whole-step FLOPs, and each
    kernel's ``(FLOPs, bytes)`` over all layers. ``prefill_rows``:
    ``(start, c)`` of every live prefill row; ``decode_lengths``: the keys
    each live decode row attends, the new token included."""
    m = Widths.of(cfg)
    pf, pb = prefill_attention(m, prefill_rows)
    df, db = decode_attention(m, decode_lengths)
    return (step_flops(m, prefill_rows=prefill_rows,
                       decode_lengths=decode_lengths),
            {"paged_mla_prefill_attention": (m.layers * pf, m.layers * pb),
             "paged_mla_decode_attention": (m.layers * df, m.layers * db)})

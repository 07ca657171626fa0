"""The plain float32 reference of the dense decoder the configurations
state, and its control in a lower precision.

It imports nothing of the program. It draws its weights from the seed with
``weights.draw_leaf`` (the values the program was given, never read back
from it), runs the whole sequence at once, causally, one layer at a time,
in float32 at matmul precision "highest", and reads the logits only where a
token was served. The block (see each configuration's ``assumed``): RMSNorm
before attention and before the MLP, rotary embedding over the whole head in
split halves, grouped-query attention, SwiGLU, untied head, no biases.

``control=True`` computes the same with every projection's inputs and
weights rounded to float8 e4m3 (per-token and per-output-channel scales),
accumulating in float32: the next precision below the bf16 the
configurations serve in.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: the parameter tree the reference computes; anything else is refused
DENSE_LEAVES = {"embedding", "lm_head", "ln_f", "layers/ln1", "layers/ln2",
                "layers/attn/wq", "layers/attn/wk", "layers/attn/wv",
                "layers/attn/wo", "layers/ffn/w_gate", "layers/ffn/w_up",
                "layers/ffn/w_down"}
#: sequences are padded to a multiple of this, so few shapes compile
LEN_BUCKET = 512
#: query rows attended at once
Q_BLOCK = 512
#: served positions are padded to a multiple of this
POS_BUCKET = 128


def check_tree(specs: dict) -> None:
    if set(specs) != DENSE_LEAVES:
        raise ValueError(
            "the program's parameters are not the dense decoder the "
            f"reference computes: extra {sorted(set(specs) - DENSE_LEAVES)},"
            f" missing {sorted(DENSE_LEAVES - set(specs))}")


def _f8(x, axis):
    """x rounded to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, control: bool):
    """x (..., K) @ w (K, N); in the control both sides go through float8
    (x per token, w per output column)."""
    if control:
        x = _f8(x, -1)
        w = _f8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (B, S, H, d), pos (S,): split-half rotation over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v):
    """Causal attention. q (B, S, H, d); k, v (B, S, KV, d)."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                        precision=HIGHEST) / math.sqrt(d)
        qpos = lo + jnp.arange(qb.shape[1])
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, axis=1)


@dataclasses.dataclass(frozen=True)
class Block:
    """What the reference needs of a configuration, hashable so that it can
    be a static argument of the jitted pieces."""
    vocab: int
    eps: float
    theta: float
    leaves: tuple          # ((name, shape, dtype name), ...)

    @classmethod
    def of(cls, cfg: dict, specs: dict) -> "Block":
        check_tree(specs)
        return cls(int(cfg["vocab_size"]), float(cfg["norm_eps"]),
                   float(cfg["rope_theta"]),
                   tuple(sorted((k, tuple(s), jnp.dtype(d).name)
                                for k, (s, d) in specs.items())))

    @property
    def layers(self) -> int:
        return dict((n, s) for n, s, _ in self.leaves)["layers/ln1"][0]

    def draw(self, key, name, layer=None):
        shape, dtype = {n: (s, d) for n, s, d in self.leaves}[name]
        return weights.draw_leaf(key, name, shape, jnp.dtype(dtype),
                                 self.vocab, layer=layer)


@functools.partial(jax.jit, static_argnames=("blk", "control"))
def _layer(x, key, layer, *, blk, control):
    def w(name):
        return blk.draw(key, name, layer)

    b, s, dm = x.shape
    pos = jnp.arange(s)
    wq, wk, wv, wo = (w("layers/attn/wq"), w("layers/attn/wk"),
                      w("layers/attn/wv"), w("layers/attn/wo"))
    h, hd = wq.shape[1], wq.shape[2]
    kvh = wk.shape[1]
    a = _rmsnorm(x, w("layers/ln1"), blk.eps)
    q = _mm(a, wq.reshape(dm, h * hd), control).reshape(b, s, h, hd)
    k = _mm(a, wk.reshape(dm, kvh * hd), control).reshape(b, s, kvh, hd)
    v = _mm(a, wv.reshape(dm, kvh * hd), control).reshape(b, s, kvh, hd)
    o = _attend(_rope(q, pos, blk.theta), _rope(k, pos, blk.theta), v)
    x = x + _mm(o.reshape(b, s, h * hd), wo.reshape(h * hd, dm), control)
    m = _rmsnorm(x, w("layers/ln2"), blk.eps)
    gate = _mm(m, w("layers/ffn/w_gate"), control)
    up = _mm(m, w("layers/ffn/w_up"), control)
    return x + _mm(jax.nn.silu(gate) * up, w("layers/ffn/w_down"), control)


@functools.partial(jax.jit, static_argnames=("blk",))
def _embed(tokens, key, *, blk):
    return jnp.take(blk.draw(key, "embedding"), tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("blk", "control"))
def _logits(hidden, key, *, blk, control):
    head = blk.draw(key, "lm_head")[:, :blk.vocab]
    return _mm(_rmsnorm(hidden, blk.draw(key, "ln_f"), blk.eps), head,
               control)


def logits_at(cfg: dict, specs: dict, seed: int, sequences: list,
              control: bool = False) -> list:
    """Float32 logits ``(K, vocab)`` of each sequence at its positions.

    ``sequences``: ``(tokens, positions)`` pairs: the whole token sequence
    the program was given for one request, and the K places whose next
    token it served. All run as one batch, padded to a multiple of
    ``LEN_BUCKET`` tokens (positions to one of ``POS_BUCKET``), so that few
    shapes ever compile."""
    blk = Block.of(cfg, specs)
    key = weights.base_key(seed)
    longest = max(len(t) for t, _ in sequences)
    s = -(-longest // LEN_BUCKET) * LEN_BUCKET
    toks = np.zeros((len(sequences), s), np.int32)
    for i, (t, _) in enumerate(sequences):
        toks[i, :len(t)] = t
    out = []
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(toks), key, blk=blk)
        for layer in range(blk.layers):
            x = _layer(x, key, jnp.int32(layer), blk=blk, control=control)
        for i, (_, pos) in enumerate(sequences):
            k = len(pos)
            padded = np.zeros(-(-k // POS_BUCKET) * POS_BUCKET, np.int32)
            padded[:k] = pos
            h = x[i, jnp.asarray(padded)]
            out.append(np.asarray(_logits(h, key, blk=blk,
                                          control=control))[:k])
    return out

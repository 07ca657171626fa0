"""What every block's plain float32 reference shares, and its control in a
lower precision. A block (``blocks/<name>.py``) brings its layer function;
this module brings the rest.

It imports nothing of the program. It draws its weights from the seed with
``weights.draw_leaf`` (the values the program was given, never read back
from it), runs the whole sequence at once, causally, one layer at a time,
in float32 at matmul precision "highest", and reads the logits only where a
token was served. A layer function builds on the projection ``mm``, RMSNorm,
rotary embedding over the whole head in split halves, and causal attention.

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
#: sequences are padded to a multiple of this, so few shapes compile
LEN_BUCKET = 512
#: query rows attended at once
Q_BLOCK = 512
#: served positions are padded to a multiple of this
POS_BUCKET = 128


def f8(x, axis):
    """x rounded to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(x, w, control: bool):
    """x (..., K) @ w (K, N); in the control both sides go through float8
    (x per token, w per output column)."""
    if control:
        x = f8(x, -1)
        w = f8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (B, S, H, d), pos (S,): split-half rotation over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attend(q, k, v):
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
    layers: int            # how many times the layer function runs
    leaves: tuple          # ((name, shape, dtype name), ...)

    @classmethod
    def of(cls, cfg: dict, specs: dict, layers: int) -> "Block":
        return cls(int(cfg["vocab_size"]), float(cfg["norm_eps"]),
                   float(cfg["rope_theta"]), int(layers),
                   tuple(sorted((k, tuple(s), jnp.dtype(d).name)
                                for k, (s, d) in specs.items())))

    def draw(self, key, name, layer=None):
        shape, dtype = {n: (s, d) for n, s, d in self.leaves}[name]
        return weights.draw_leaf(key, name, shape, jnp.dtype(dtype),
                                 self.vocab, layer=layer)


@functools.partial(jax.jit, static_argnames=("blk",))
def _embed(tokens, key, *, blk):
    return jnp.take(blk.draw(key, "embedding"), tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("blk", "control"))
def _logits(hidden, key, *, blk, control):
    head = blk.draw(key, "lm_head")[:, :blk.vocab]
    return mm(rmsnorm(hidden, blk.draw(key, "ln_f"), blk.eps), head,
              control)


def logits_at(blk: Block, layer, seed: int, sequences: list,
              control: bool = False) -> list:
    """Float32 logits ``(K, vocab)`` of each sequence at its positions:
    the ``embedding``, then ``layer(x, key, n, blk=blk, control=control)``
    (the block's jitted layer function) for each layer ``n`` in turn, then
    the final norm ``ln_f`` and the head ``lm_head``.

    ``sequences``: ``(tokens, positions)`` pairs: the whole token sequence
    the program was given for one request, and the K places whose next
    token it served. All run as one batch, padded to a multiple of
    ``LEN_BUCKET`` tokens (positions to one of ``POS_BUCKET``), so that few
    shapes ever compile."""
    key = weights.base_key(seed)
    longest = max(len(t) for t, _ in sequences)
    s = -(-longest // LEN_BUCKET) * LEN_BUCKET
    toks = np.zeros((len(sequences), s), np.int32)
    for i, (t, _) in enumerate(sequences):
        toks[i, :len(t)] = t
    out = []
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(toks), key, blk=blk)
        for n in range(blk.layers):
            x = layer(x, key, jnp.int32(n), blk=blk, control=control)
        for i, (_, pos) in enumerate(sequences):
            k = len(pos)
            padded = np.zeros(-(-k // POS_BUCKET) * POS_BUCKET, np.int32)
            padded[:k] = pos
            h = x[i, jnp.asarray(padded)]
            out.append(np.asarray(_logits(h, key, blk=blk,
                                          control=control))[:k])
    return out

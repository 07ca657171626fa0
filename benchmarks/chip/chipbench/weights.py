"""Seeded weights, made by the benchmark: the same values for the program
(all at once, on the device, in the type they are served in) and for the
float32 reference (one layer at a time).

Each leaf of the program's parameter tree is drawn from a key folded from
the seed and the leaf's path; a leaf stacked over layers (one in a group
that the configuration's block lists as stacked, ``layers/`` for the dense
block) draws layer ``l`` from that key folded with ``l``. A value is the
sum of four random bytes, centred (an Irwin-Hall draw, near normal within
3.5 sigma), times a scale with few bits: all of it integer arithmetic or
exact in float32, so the values do not depend on how a compiler fuses the
draw, and the reference's one-layer draw equals the program's bit for bit.
Matrices have a standard deviation of 0.0226 (the program's own initialiser
uses 0.02); norm scales are 1 plus 0.108 of it, so that a norm that ignored
its weight would show. Rows of the embedding and columns of the head past
the true vocabulary (the padding up to ``padded_vocab``) are zero: a padded
id is never looked up and never wins the argmax.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

#: a centred sum of four bytes has standard deviation 147.8; times these
#: (5 * 2**-15 and 3 * 2**-12, exact in float32) it is 0.0226 and 0.108
MATRIX_SCALE = 5 * 2.0 ** -15
NORM_SCALE = 3 * 2.0 ** -12


def base_key(seed: int) -> jax.Array:
    """A key for any whole ``seed`` up to 2**62: the low 31 bits seed the
    key and the rest is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def is_norm(name: str) -> bool:
    last = name.rsplit("/", 1)[-1]
    return last.startswith("ln") or last.endswith("_norm")


def _draw(key, name: str, shape, dtype, vocab: int):
    b = jax.random.bits(key, shape, jnp.uint32)
    four = sum((b >> s) & 0xFF for s in (0, 8, 16, 24))
    z = (four.astype(jnp.int32) - 510).astype(jnp.float32)
    if is_norm(name):
        v = 1.0 + z * NORM_SCALE
    else:
        v = z * MATRIX_SCALE
    if name == "embedding":
        v = v * (jnp.arange(shape[0]) < vocab)[:, None]
    elif name == "lm_head":
        v = v * (jnp.arange(shape[-1]) < vocab)[None, :]
    return v.astype(dtype)


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def make_params(abstract, seed: int, vocab: int, stacked: tuple):
    """The whole tree shaped like ``abstract`` (the program's
    ``ShapeDtypeStruct`` tree), made on the default device in one jitted
    call. ``stacked``: the groups (name prefixes such as ``"layers/"``)
    whose leaves are stacked over layers and drawn one layer at a time."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    specs = [(leaf_name(p), s.shape, s.dtype) for p, s in flat]

    def build(key):
        leaves = []
        for name, shape, dtype in specs:
            k = _leaf_key(key, name)
            if name.startswith(tuple(stacked)):
                leaves.append(jax.vmap(
                    lambda l, k=k, name=name, shape=shape, dtype=dtype: _draw(
                        jax.random.fold_in(k, l), name, shape[1:], dtype,
                        vocab))(jnp.arange(shape[0])))
            else:
                leaves.append(_draw(k, name, shape, dtype, vocab))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(base_key(seed))


def leaf_specs(abstract) -> dict:
    """``{name: (shape, dtype)}`` of the program's parameter tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract)
    return {leaf_name(p): (tuple(s.shape), s.dtype) for p, s in flat}


def draw_leaf(key, name: str, shape, dtype, vocab: int, layer=None):
    """One leaf (or layer ``layer`` of a stacked one) as float32, rounded
    through the type it is served in: the values the program holds."""
    k = _leaf_key(key, name)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
        shape = shape[1:]
    return _draw(k, name, shape, dtype, vocab).astype(jnp.float32)

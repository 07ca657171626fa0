"""Pallas TPU flash-decode: one query token against a long KV cache.

This is the kernel the paper's LiveCaptions analysis motivates (§4.1/§4.2):
decode-phase attention is many tiny kernels on GPU, starved under concurrent
load and inefficient even alone. The TPU adaptation fuses the entire decode
attention for all G query heads of a KV head into ONE kernel: grid
(B, KV, nS) with the sequence tile innermost, online softmax carried in VMEM
scratch, and the per-row valid length read from SMEM — one launch instead of
O(S/page) launches, MXU-aligned (G×d by d×S_tile products).

Rotary embedding is fused: when ``rope_theta`` is given, the query is rotated
in-kernel at position ``lengths - 1`` (the new token's absolute position), so
decode needs no separate RoPE launch before attention. Cached keys are
already rotated at write time, so only q needs the rotation here.

Non-divisible sequence lengths are handled by padding the KV cache up to the
next ``s_block`` multiple — padded positions sit beyond every row's valid
length and are masked by the online softmax, so the result is exact.

Layout: q (B, H, d); k/v (B, KV, S, d); lengths (B,) int32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _rope_rotate(q, position, theta: float):
    """Rotate (G, d) query rows to ``position`` (scalar int32) in-kernel."""
    g, d = q.shape
    half = d // 2
    # Mosaic's iota is integer-only: build the index in int32, then cast
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(
        jnp.float32)
    inv = jnp.exp(idx * (-2.0 / d) * math.log(theta))        # theta^(-2i/d)
    ang = position.astype(jnp.float32) * inv                 # (1, half)
    sin = jnp.sin(ang)
    cos = jnp.cos(ang)
    q1 = q[:, :half]
    q2 = q[:, half:]
    return jnp.concatenate([q1 * cos - q2 * sin, q1 * sin + q2 * cos], axis=1)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, s_block: int, num_s_steps: int, g: int,
            rope_theta: float | None):
    b = pl.program_id(0)
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(sj * s_block < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                  # (G, d)
        if rope_theta is not None:
            q = _rope_rotate(q, length - 1, rope_theta)
        q = q * scale
        k = k_ref[0, 0].astype(jnp.float32)                  # (sb, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, sb)
        pos = sj * s_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # (sb, d)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(sj == num_s_steps - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("s_block", "rope_theta",
                                             "interpret"))
def decode_attention(q, k, v, lengths, *, s_block: int | None = None,
                     rope_theta: float | None = None,
                     interpret: bool = False):
    """q: (B, H, d); k/v: (B, KV, S, d); lengths: (B,) -> (B, H, d).

    ``s_block=None`` consults the roofline autotuner (kernels/autotune.py).
    ``rope_theta``: fuse rotary embedding of q at position ``lengths - 1``.
    """
    b, h, d = q.shape
    kv, s = k.shape[1], k.shape[2]
    g = h // kv
    if s_block is None:
        from repro.kernels import autotune
        s_block = autotune.best_config(
            "decode_attention",
            {"b": b, "kv": kv, "g": g, "s": s, "d": d})["s_block"]
    s_block = min(s_block, s)
    if s % s_block:  # pad KV up to a block multiple; padding is masked
        pad = s_block - s % s_block
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        s = s + pad
    ns = s // s_block
    scale = 1.0 / math.sqrt(d)

    qg = q.reshape(b, kv, g, d)
    kernel = functools.partial(_kernel, scale=scale, s_block=s_block,
                               num_s_steps=ns, g=g, rope_theta=rope_theta)
    out = pl.pallas_call(
        kernel,
        grid=(b, kv, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths, whole array
            pl.BlockSpec((1, 1, g, d), lambda b_, k_, j: (b_, k_, 0, 0)),
            pl.BlockSpec((1, 1, s_block, d), lambda b_, k_, j: (b_, k_, j, 0)),
            pl.BlockSpec((1, 1, s_block, d), lambda b_, k_, j: (b_, k_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, k_, j: (b_, k_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(b, h, d)

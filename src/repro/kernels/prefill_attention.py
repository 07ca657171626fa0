"""Pallas TPU prefill-chunk flash attention: a C-token chunk vs the cache.

The serving engine's chunked prefill attends each C-token chunk against the
full KV cache (history + the chunk itself, already scattered in). The jnp
lowering (`models.attention.prefill_chunk_attention_jnp`) materializes a
(B, KV, G, C, S) logits tensor — fine on CPU test shapes, hostile at serving
shapes. This kernel is the TPU path: ONE launch per (batch row, KV head)
streaming the cache in ``s_block`` tiles with an online softmax, exactly the
flash-decode scheme of :mod:`repro.kernels.decode_attention` generalized
from one query row to the chunk's C*G query rows.

Query rows are flattened (chunk token, query head) -> row ``r = c_idx*G +
g_idx`` so each row's causal horizon depends only on ``r // G``: row r may
attend cache positions ``<= start_len + r // G`` (full history plus the
chunk prefix up to and including its own token). Rotary embedding is fused:
row r's query is rotated in-kernel at absolute position ``start_len + r//G``
(cached keys are rotated at write time), so multi-slot batched prefill needs
no per-row RoPE launches.

Rows whose chunk is only partially valid (multi-slot batching pads short
rows up to the widest chunk in the dispatch) need no masking here: padded
tokens still attend a well-formed causal window, and the engine discards
their logits — while their k/v never reach the cache (the models' scatter
drops them), so no valid row ever attends a pad position.

Non-divisible cache lengths are handled by padding K/V up to the next
``s_block`` multiple — padded positions sit beyond every row's horizon and
are masked by the online softmax, so the result is exact.

Layout: q (B, H, C, d) head-major; k/v (B, KV, S, d); start_len (B,) int32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _rope_rotate_rows(q, positions, theta: float):
    """Rotate (R, d) query rows, row r at ``positions[r]`` ((R, 1) int32)."""
    r, d = q.shape
    half = d // 2
    # Mosaic's iota is integer-only: build the index in int32, then cast
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(
        jnp.float32)
    inv = jnp.exp(idx * (-2.0 / d) * math.log(theta))        # theta^(-2i/d)
    ang = positions.astype(jnp.float32) * inv                # (R, half)
    sin = jnp.sin(ang)
    cos = jnp.cos(ang)
    q1 = q[:, :half]
    q2 = q[:, half:]
    return jnp.concatenate([q1 * cos - q2 * sin, q1 * sin + q2 * cos], axis=1)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, s_block: int, num_s_steps: int, c: int, g: int,
            rope_theta: float | None):
    b = pl.program_id(0)
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = len_ref[b]

    # every tile at or below the chunk's last token participates
    @pl.when(sj * s_block < start + c)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                  # (C*G, d)
        rows = jax.lax.broadcasted_iota(jnp.int32, (c * g, 1), 0)
        qpos = start + rows // g                             # (C*G, 1)
        if rope_theta is not None:
            q = _rope_rotate_rows(q, qpos, rope_theta)
        q = q * scale
        k = k_ref[0, 0].astype(jnp.float32)                  # (sb, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (C*G, sb)
        pos = sj * s_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos <= qpos, s, NEG_INF)               # per-row horizon
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
def prefill_attention(q, k, v, start_len, *, s_block: int | None = None,
                      rope_theta: float | None = None,
                      interpret: bool = False):
    """q: (B, H, C, d); k/v: (B, KV, S, d) with the chunk's keys/values
    already written at ``start_len .. start_len+C-1``; start_len: (B,)
    -> (B, H, C, d).

    ``s_block=None`` consults the roofline autotuner (kernels/autotune.py).
    ``rope_theta``: fuse rotary embedding of chunk query j at absolute
    position ``start_len + j``.
    """
    b, h, c, d = q.shape
    kv, s = k.shape[1], k.shape[2]
    g = h // kv
    if s_block is None:
        from repro.kernels import autotune
        s_block = autotune.best_config(
            "prefill_attention",
            {"b": b, "kv": kv, "g": g, "c": c, "s": s, "d": d})["s_block"]
    s_block = min(s_block, s)
    if s % s_block:  # pad KV up to a block multiple; padding is masked
        pad = s_block - s % s_block
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        s = s + pad
    ns = s // s_block
    scale = 1.0 / math.sqrt(d)

    # (B, H, C, d) -> (B, KV, C*G, d): row r = chunk token r//G, head r%G
    qr = (q.reshape(b, kv, g, c, d).transpose(0, 1, 3, 2, 4)
          .reshape(b, kv, c * g, d))
    kernel = functools.partial(_kernel, scale=scale, s_block=s_block,
                               num_s_steps=ns, c=c, g=g,
                               rope_theta=rope_theta)
    out = pl.pallas_call(
        kernel,
        grid=(b, kv, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # start_len, whole array
            pl.BlockSpec((1, 1, c * g, d), lambda b_, k_, j: (b_, k_, 0, 0)),
            pl.BlockSpec((1, 1, s_block, d), lambda b_, k_, j: (b_, k_, j, 0)),
            pl.BlockSpec((1, 1, s_block, d), lambda b_, k_, j: (b_, k_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c * g, d),
                               lambda b_, k_, j: (b_, k_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, c * g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((c * g, d), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(start_len, jnp.int32), qr, k, v)
    return (out.reshape(b, kv, c, g, d).transpose(0, 1, 3, 2, 4)
            .reshape(b, h, c, d))

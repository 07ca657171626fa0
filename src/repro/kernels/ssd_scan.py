"""Pallas TPU kernel for the Mamba2 SSD intra-chunk quadratic + chunk state.

One grid cell computes one (batch·chunk, head-block): the (Q, Q) masked
decay-weighted score matrix (shared CB term per head group), the intra-chunk
output y = scores @ x, and the end-of-chunk state contribution
state = (B^T · (w ⊙ x)). Heads are blocked so the (Q, Q, hb) decay tensor
stays inside VMEM; Q and the head block are MXU/VPU aligned.

Layouts: x (M, Q, H, P); dt/cum (M, Q, H); b_/c_ (M, Q, N)
with M = batch*num_chunks flattened. Outputs: y (M, Q, H, P),
state (M, H, P, N). The wrapper hands the kernel x, dt, cum and y
head-major ((M, H, Q, P) and (M, H, Q)): a head block then tiles a major
or second-minor dim, where the TPU lowering takes any multiple of 8, and
each head's (Q, P) / (1, Q) slab is a whole tile, never a strided slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import largest_divisor as _largest_divisor


def _kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, st_ref, *,
            q: int, hb: int, p: int, n: int):
    dt = dt_ref[0].astype(jnp.float32)          # (hb, Q)
    cum = cum_ref[0].astype(jnp.float32)        # (hb, Q)
    b_ = b_ref[0].astype(jnp.float32)           # (Q, N)
    c_ = c_ref[0].astype(jnp.float32)           # (Q, N)

    cb = jax.lax.dot_general(c_, b_, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = row >= col

    for h in range(hb):  # static unroll over the head block
        cum_h = cum[h:h + 1]                                   # (1, Q)
        dt_h = dt[h:h + 1]                                     # (1, Q)
        seg = cum_h.T - cum_h                                  # (Q, Q)
        decay = jnp.where(tri, jnp.exp(seg), 0.0)
        scores = cb * decay * dt_h                             # (Q, Q)
        xh = x_ref[0, h].astype(jnp.float32)                   # (Q, P)
        y = jax.lax.dot_general(scores, xh, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y_ref[0, h] = y.astype(y_ref.dtype)
        wgt = jnp.exp(cum[h:h + 1, q - 1:q] - cum_h) * dt_h    # (1, Q)
        xw = xh * wgt.T                                        # (Q, P)
        st = jax.lax.dot_general(xw, b_, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (P, N)
        st_ref[0, h] = st.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def ssd_chunk_scan(x, dt, cum, b_, c_, *, head_block: int | None = None,
                   interpret: bool = False):
    """x: (M, Q, H, P); dt/cum: (M, Q, H); b_/c_: (M, Q, N).

    Returns (y (M, Q, H, P), state (M, H, P, N)). ``head_block=None``
    consults the roofline autotuner; a head count not divisible by the block
    falls back to the largest valid divisor instead of asserting.
    """
    m, q, h, p = x.shape
    n = b_.shape[-1]
    if head_block is None:
        from repro.kernels import autotune
        head_block = autotune.best_config(
            "ssd_chunk_scan",
            {"m": m, "q": q, "h": h, "p": p, "n": n})["head_block"]
    hb = _largest_divisor(h, min(head_block, h))
    nh = h // hb

    kernel = functools.partial(_kernel, q=q, hb=hb, p=p, n=n)
    y, st = pl.pallas_call(
        kernel,
        grid=(m, nh),
        in_specs=[
            pl.BlockSpec((1, hb, q, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, hb, q), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, hb, q), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, q, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, hb, p, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, h, q, p), x.dtype),
            jax.ShapeDtypeStruct((m, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), cum.transpose(0, 2, 1),
      b_, c_)
    return y.transpose(0, 2, 1, 3), st

"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Optional "stage" mesh axis: layers are split into S contiguous stages; a
microbatched forward pushes activations stage-to-stage with ppermute. The
bubble fraction is (S-1)/(S-1+M) for M microbatches — reported by
``bubble_fraction`` and exercised by tests on a multi-device host mesh.

This demonstrates the PP axis for the parallelism matrix (DESIGN.md §5); the
default 40-cell dry-run table uses DP×TP(×EP) without PP.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    s, m = num_stages, num_microbatches
    return (s - 1) / (s - 1 + m)


def pipelined_forward(layer_fn: Callable, params_stacked, x,
                      mesh: Mesh, *, num_microbatches: int,
                      stage_axis: str = "stage"):
    """Run ``layer_fn`` stacks split over the ``stage`` mesh axis.

    layer_fn(layer_params, h) -> h, applied L/S times per stage.
    params_stacked: pytree with leading layer axis L (L % S == 0).
    x: (B, ...) global batch; B % num_microbatches == 0.

    Returns y with the same shape as x. GPipe schedule: each stage processes
    microbatch m at step t = stage + m; activations move via ppermute.
    """
    num_stages = mesh.shape[stage_axis]
    l = jax.tree.leaves(params_stacked)[0].shape[0]
    assert l % num_stages == 0, (l, num_stages)
    b = x.shape[0]
    assert b % num_microbatches == 0
    mb = b // num_microbatches

    # reshape params to (S, L/S, ...) so each stage holds its slice
    def split(p):
        return p.reshape((num_stages, l // num_stages) + p.shape[1:])
    params_staged = jax.tree.map(split, params_stacked)

    pspec_params = jax.tree.map(lambda _: P(stage_axis), params_staged)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(stage_axis), params_staged),
                  P()),
        out_specs=P(),
        check_vma=False)
    def run(params_local, x_local):
        # params_local: (1, L/S, ...); x_local: full batch (replicated)
        stage_params = jax.tree.map(lambda p: p[0], params_local)
        stage_id = jax.lax.axis_index(stage_axis)
        micro = x_local.reshape((num_microbatches, mb) + x_local.shape[1:])

        def stage_apply(h):
            def body(h, lp):
                return layer_fn(lp, h), None
            h, _ = jax.lax.scan(body, h, stage_params)
            return h

        num_steps = num_microbatches + num_stages - 1
        buf = jnp.zeros((mb,) + x_local.shape[1:], x_local.dtype)
        outs = jnp.zeros_like(micro)

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            incoming = jnp.where(t < num_microbatches,
                                 micro[jnp.clip(t, 0, num_microbatches - 1)],
                                 jnp.zeros_like(buf))
            h_in = jnp.where(stage_id == 0, incoming, buf)
            h_out = stage_apply(h_in)
            # push to next stage
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
            buf_next = jax.lax.ppermute(h_out, stage_axis, perm)
            # last stage emits microbatch t - (S-1)
            emit_idx = t - (num_stages - 1)
            valid = jnp.logical_and(emit_idx >= 0,
                                    stage_id == num_stages - 1)
            outs = jax.lax.cond(
                jnp.any(valid),
                lambda o: o.at[jnp.clip(emit_idx, 0, num_microbatches - 1)]
                .set(jnp.where(valid, h_out, o[jnp.clip(emit_idx, 0,
                                                        num_microbatches - 1)])),
                lambda o: o,
                outs)
            return (buf_next, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs),
                                      jnp.arange(num_steps))
        # only the last stage holds real outputs; broadcast via psum-mask
        mask = (stage_id == num_stages - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * mask, stage_axis)
        return outs.reshape(x_local.shape)

    return run(params_staged, x)

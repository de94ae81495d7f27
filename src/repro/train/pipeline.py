"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Used when the `pod` axis is repurposed as a pipeline axis: each pod holds a
contiguous slice of layers; microbatches stream through stages with
`jax.lax.ppermute` hand-offs.  The steady-state schedule keeps all stages
busy except the (S-1)-bubble at the ends, the classic GPipe trade-off.

This module is self-contained (works on any mesh axis); tests exercise it on
a small host-device mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


def pipeline_forward(stage_fn: Callable, h: jax.Array, stage_params,
                     *, axis_name: str, num_stages: int,
                     num_microbatches: int) -> jax.Array:
    """Run inside shard_map: h (M, mb, L, d) microbatched activations.

    stage_fn(params, x) -> x applies THIS device's layer slice.
    stage_params: this stage's parameter slice.
    Returns outputs in original microbatch order (valid on the last stage,
    broadcast back to all stages for loss symmetry).
    """
    M, S = num_microbatches, num_stages
    stage = jax.lax.axis_index(axis_name)
    T = M + S - 1                      # total pipeline ticks

    def tick(carry, t):
        buf, outs = carry              # buf: (mb, L, d) in-flight activation
        # stage 0 injects microbatch t (if any remain)
        inject = jnp.where(t < M, t, M - 1)
        x0 = jax.lax.dynamic_index_in_dim(h, inject, axis=0, keepdims=False)
        x_in = jnp.where(stage == 0, x0, buf)
        y = stage_fn(stage_params, x_in)
        # last stage records its finished microbatch (t - (S-1))
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        record = jnp.logical_and(stage == S - 1, t >= S - 1)
        outs = jax.lax.cond(
            record,
            lambda o: jax.lax.dynamic_update_index_in_dim(o, y, out_idx, 0),
            lambda o: o, outs)
        # hand activation to the next stage
        buf = jax.lax.ppermute(y, axis_name,
                               [(i, (i + 1) % S) for i in range(S)])
        return (buf, outs), None

    buf0 = jnp.zeros_like(h[0])
    outs0 = jnp.zeros_like(h)
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
    # broadcast final outputs from the last stage to every stage
    outs = jax.lax.ppermute(outs, axis_name,
                            [((S - 1 + i) % S, i) for i in range(S)])
    return outs


def make_pipelined_apply(stage_fn: Callable, mesh, *, axis_name: str = "pod",
                         num_microbatches: int = 4):
    """Wrap a per-stage layer fn into a full pipelined apply via shard_map."""
    S = mesh.shape[axis_name]

    def apply(stacked_params, h):
        # h: (M, mb, L, d) replicated; params stacked (S, ...) sharded on axis
        def inner(params_slice, h_rep):
            params_slice = jax.tree.map(lambda x: x[0], params_slice)
            return pipeline_forward(stage_fn, h_rep, params_slice,
                                    axis_name=axis_name, num_stages=S,
                                    num_microbatches=num_microbatches)

        pspec = jax.tree.map(lambda _: PS(axis_name), stacked_params)
        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(pspec, PS()), out_specs=PS(),
                             check_vma=False)(stacked_params, h)

    return apply

"""Pipeline parallelism: GPipe-style microbatch streaming over a mesh axis.

Each pipeline stage owns a contiguous slice of layers; microbatches stream
stage-to-stage via neighbor `ppermute` (the `collective-permute` chains the
tracer classifies as `pipeline` traffic).  The schedule runs M + P - 1
ticks; bubble fraction (P-1)/(M+P-1) is the textbook GPipe overhead.

This is the optional PP building block: the assigned shapes are covered by
FSDP x TP (+2 pods), but at >4 pods the cross-pod DCI makes FSDP gathers
expensive and stage-parallelism over `pod` becomes the right trade — the
cost model prices both so the choice is quantitative.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, mesh,
                   axis: str = "model"):
    """Run microbatches through P pipeline stages.

    stage_fn(params_slice, h) -> h       (one stage's layers)
    stage_params: pytree whose leaves have leading dim P (one slice/stage)
    x_micro:      [M, mb, ...] microbatches
    Returns y [M, mb, ...] after all P stages.
    """
    p_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    M = x_micro.shape[0]
    ticks = M + p_size - 1
    fwd_perm = [(i, i + 1) for i in range(p_size - 1)]

    def run(params_loc, x_loc):
        # params_loc: this stage's slice (leading dim 1); x_loc: full [M,...]
        params_me = jax.tree.map(lambda a: a[0], params_loc)
        idx = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(x_loc[0])                  # stage input register
        out = jnp.zeros_like(x_loc)
        for t in range(ticks):
            # stage 0 injects microbatch t; others use the received buffer
            mb = min(t, M - 1)
            inject = x_loc[mb]
            h_in = jnp.where(idx == 0, inject, buf)
            with jax.named_scope("pipeline_stage"):
                h_out = stage_fn(params_me, h_in)
            # last stage retires microbatch (t - (P-1)) at tick t
            retire = t - (p_size - 1)
            if 0 <= retire < M:
                out = out.at[retire].set(
                    jnp.where(idx == p_size - 1, h_out, out[retire]))
            with jax.named_scope("pipeline_hop"):
                buf = jax.lax.ppermute(h_out, axis, fwd_perm)
        # results live on the last stage; broadcast to all for the caller
        out = jax.lax.psum(
            jnp.where(idx == p_size - 1, out, jnp.zeros_like(out)), axis)
        return out

    mapped = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(axis), P()),     # params split by stage; micros replicated
        out_specs=P(),
        check_vma=False)
    return mapped(stage_params, x_micro)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)

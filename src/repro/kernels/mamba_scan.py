"""Selective-scan (Mamba-1) Pallas kernel (TPU target).

TPU-native layout (not a port of the CUDA scan):
  * inputs are the discretized terms a_bar, bx [B, S, Di, N] and the readout
    c [B, S, N] (computed by dense einsums outside — those are MXU work and
    XLA handles them well; the *scan* is the part XLA does badly),
  * the kernel works on [.., N, Di] tiles: Di on the 128 lanes and the
    state dim N (16 for falcon-mamba) on the sublanes.  With N on the lanes
    every [N] row pads to 128 lanes, 8x the VMEM, and the default tiles no
    longer fit (v5e refuses them: 257 MiB of 128 MiB VMEM),
  * grid (B, Di/blk, n_chunks): the chunk axis is sequential; the recurrent
    state h [N, blk] lives in VMEM scratch and never touches HBM between
    chunks — the XLA path writes the full [B, S, Di, N] h history,
  * within a chunk the recurrence runs as a fori_loop of VPU ops over
    timesteps; per step a_t, bx_t, c_t and y_t are read or written at a
    leading (untiled) index, so every access is whole (8,128) tiles.

`di_block` must be a multiple of 128 or all of Di to compile for the TPU;
interpret mode takes any divisor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, bx_ref, c_ref, y_ref, h_ref, *, chunk: int):
    ci = pl.program_id(2)   # chunk axis is innermost (sequential, carries h)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        h = a_ref[0, t] * h + bx_ref[0, t]     # [N, blk]
        # c_t [N, 1] broadcasts over the lanes; the sum runs over sublanes
        y_ref[0, t] = jnp.sum(h * c_ref[0, t], axis=0, keepdims=True)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def mamba_scan(a_bar, bx, c, *, chunk=64, di_block=512, interpret=False):
    """h_t = a_t * h_{t-1} + bx_t;  y_t[d] = sum_n h_t[d,n] * c_t[n].

    a_bar, bx: [B, S, Di, N] fp32;  c: [B, S, N] fp32  ->  y [B, S, Di] fp32.

    The default tiles keep a, bx (double-buffered) at 8 MiB of VMEM for
    N=16, inside v5e's 16 MiB scoped default.
    """
    B, S, Di, N = a_bar.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    di_block = min(di_block, Di)
    while Di % di_block:
        di_block //= 2
    n_chunks = S // chunk
    n_di = Di // di_block

    grid = (B, n_di, n_chunks)   # chunks innermost: h carried across them
    tile = pl.BlockSpec((1, chunk, N, di_block),
                        lambda b, di, ci: (b, ci, 0, di))
    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            tile,
            tile,
            pl.BlockSpec((1, chunk, N, 1), lambda b, di, ci: (b, ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, di_block),
                               lambda b, di, ci: (b, ci, 0, di)),
        out_shape=jax.ShapeDtypeStruct((B, S, 1, Di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, di_block), jnp.float32)],
        interpret=interpret,
    )(a_bar.swapaxes(2, 3), bx.swapaxes(2, 3), c[..., None])
    return y.reshape(B, S, Di)

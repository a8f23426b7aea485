"""Jitted wrappers around the Pallas kernels.

`flash_attention` adapts the model's [B, S, H, D] layout + GQA + head-dim
padding (h2o-danube's 120 -> 128) to the kernel's [B, H, S, D] tiles.
The wrappers compile the Mosaic kernels (`interpret=False`) whatever the
backend; a caller without a TPU asks for the Pallas interpreter with
`interpret=True`, as the CPU tests do.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import mamba_scan as ms


def flash_attention(cfg, q, k, v, *, causal=True, window=0, q_offset=0,
                    interpret=False):
    """Model-layout wrapper: q [B,S,H,Dh], k/v [B,S,K,Dh] -> [B,S,H,Dh]."""
    B, Sq, H, Dh = q.shape
    scale = cfg.head_dim ** -0.5 if cfg is not None else Dh ** -0.5
    pad = (-Dh) % 128
    if pad:
        padw = [(0, 0), (0, 0), (0, 0), (0, pad)]
        q = jnp.pad(q, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = fa.flash_attention(qt, kt, vt, causal=causal,
                             window=int(window) if window else 0,
                             q_offset=q_offset, scale=scale,
                             interpret=interpret)
    out = out.transpose(0, 2, 1, 3)
    if pad:
        out = out[..., :Dh]
    return out


def mamba_scan(a_bar, bx, c, *, interpret=False, chunk=64, di_block=512):
    return ms.mamba_scan(a_bar.astype(jnp.float32), bx.astype(jnp.float32),
                         c.astype(jnp.float32), chunk=chunk,
                         di_block=di_block, interpret=interpret)

"""Mamba-1 selective SSM block (falcon-mamba / hymba mamba heads).

Training/prefill uses a chunked scan: an outer `lax.scan` over sequence
chunks carries the recurrent state h [B, d_inner, N]; within a chunk the
recurrence is evaluated with a numerically-stable `associative_scan`.
This XLA scan is the model path on every backend, the TPU included.
The Pallas kernel in `repro.kernels.mamba_scan` (same chunking, explicit
VMEM tiles) compiles for v5e but is not wired in here.

Decode carries (conv_state [B, d_conv-1, d_inner], ssm_state [B, d_inner, N]).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.meta import ParamMeta


def dt_rank(cfg) -> int:
    return max(1, math.ceil(cfg.d_model / 16))


def ssm_meta(cfg, d_model=None):
    d = d_model or cfg.d_model
    di = cfg.expand * d
    n = cfg.ssm_state
    r = max(1, math.ceil(d / 16))
    return {
        "in_proj": ParamMeta((d, 2 * di), ("embed", "inner")),
        "conv_w": ParamMeta((cfg.d_conv, di), (None, "inner"), scale=0.5),
        "conv_b": ParamMeta((di,), ("inner",), init="zeros"),
        "x_proj": ParamMeta((di, r + 2 * n), ("inner", None)),
        "dt_w": ParamMeta((r, di), (None, "inner")),
        "dt_bias": ParamMeta((di,), ("inner",), init="constant", scale=-4.6),
        "a_log": ParamMeta((di, n), ("inner", None), init="a_log"),
        "d_skip": ParamMeta((di,), ("inner",), init="ones"),
        "out_proj": ParamMeta((di, d), ("inner", "embed")),
    }


def _ssm_inputs(cfg, p, xc, d):
    """Common pre-scan computation. xc [B, S, di] (post-conv, post-silu).

    Returns (a_bar, bx, c) with
      a_bar [B,S,di,N] = exp(delta * A), bx [B,S,di,N], c [B,S,N].
    """
    r = max(1, math.ceil(d / 16))
    n = cfg.ssm_state
    proj = jnp.einsum("bsd,dr->bsr", xc, p["x_proj"].astype(xc.dtype))
    dt_raw, b_ssm, c_ssm = jnp.split(proj, [r, r + n], axis=-1)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_raw, p["dt_w"].astype(xc.dtype)).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))                      # [B,S,di]
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                 # [di,N]
    a_bar = jnp.exp(delta[..., None] * a)                        # [B,S,di,N]
    bx = (delta * xc.astype(jnp.float32))[..., None] \
        * b_ssm.astype(jnp.float32)[..., None, :]                # [B,S,di,N]
    return a_bar, bx, c_ssm.astype(jnp.float32)


def _conv1d_causal(cfg, p, x, conv_state=None):
    """Depthwise causal conv over S. x [B,S,di] -> [B,S,di].

    conv_state [B, d_conv-1, di] prepends history (decode/chunk-streaming).
    """
    dc = cfg.d_conv
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], dc - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    w = p["conv_w"].astype(x.dtype)                              # [dc, di]
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(dc))
    return out + p["conv_b"].astype(x.dtype)


def _chunk_scan(a_bar, bx, h0):
    """Within-chunk associative scan. a_bar/bx [B,C,di,N], h0 [B,di,N].

    Returns (h_all [B,C,di,N], h_last).
    """
    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    a_cum, b_cum = jax.lax.associative_scan(combine, (a_bar, bx), axis=1)
    h_all = a_cum * h0[:, None] + b_cum
    return h_all, h_all[:, -1]


def apply_ssm(cfg, p, x, *, chunk=256, d_model=None):
    """Full-sequence selective SSM. x [B,S,D] -> [B,S,D].

    With `cfg.ssm_inloop`, the discretized terms a_bar/bx [B,C,di,N] are
    computed per chunk *inside* the scan instead of materializing the full
    [B,S,di,N] tensors up front (S/C times smaller live footprint and HBM
    traffic — the XLA stand-in for what the Pallas kernel does in VMEM).
    """
    with jax.named_scope("ssm"):
        d = d_model or cfg.d_model
        di = cfg.expand * d
        dt = x.dtype
        B, S, _ = x.shape
        xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(dt))
        x_in, z = jnp.split(xz, 2, axis=-1)
        xc = jax.nn.silu(_conv1d_causal(cfg, p, x_in))

        chunk = min(chunk, S)
        while S % chunk:
            chunk //= 2
        nck = S // chunk
        reshape = lambda t: t.reshape(B, nck, chunk, *t.shape[2:]).transpose(
            1, 0, 2, *range(3, t.ndim + 1))
        h0 = jnp.zeros((B, di, cfg.ssm_state), jnp.float32)

        def scan_chunk(h, a_c, bx_c, c_c):
            h_all, h_last = _chunk_scan(a_c, bx_c, h)
            y_c = jnp.einsum("bcdn,bcn->bcd", h_all, c_c)        # [B,C,di]
            return h_last, y_c

        if cfg.ssm_inloop:
            def outer(h, xc_c):
                a_c, bx_c, c_c = _ssm_inputs(cfg, p, xc_c, d)
                return scan_chunk(h, a_c, bx_c, c_c)
            _, y = jax.lax.scan(outer, h0, reshape(xc))
        else:
            a_bar, bx, c = _ssm_inputs(cfg, p, xc, d)

            def outer(h, args):
                return scan_chunk(h, *args)
            _, y = jax.lax.scan(outer, h0,
                                (reshape(a_bar), reshape(bx), reshape(c)))
        y = y.transpose(1, 0, 2, 3).reshape(B, S, di)
        y = y + xc.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)
        y = y.astype(dt) * jax.nn.silu(z)
        return jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(dt))


def init_ssm_state(cfg, batch, d_model=None, dtype=jnp.float32):
    d = d_model or cfg.d_model
    di = cfg.expand * d
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, di), dtype),
        "ssm": jnp.zeros((batch, di, cfg.ssm_state), jnp.float32),
    }


def decode_ssm(cfg, p, x, state, *, d_model=None):
    """Single-token SSM step. x [B,1,D] -> ([B,1,D], new_state)."""
    with jax.named_scope("ssm_decode"):
        d = d_model or cfg.d_model
        dt = x.dtype
        xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(dt))
        x_in, z = jnp.split(xz, 2, axis=-1)                      # [B,1,di]
        xc = jax.nn.silu(_conv1d_causal(cfg, p, x_in, conv_state=state["conv"]))
        new_conv = jnp.concatenate(
            [state["conv"][:, 1:], x_in.astype(state["conv"].dtype)], axis=1)
        a_bar, bx, c = _ssm_inputs(cfg, p, xc, d)                # [B,1,di,N]
        h = a_bar[:, 0] * state["ssm"] + bx[:, 0]                # [B,di,N]
        y = jnp.einsum("bdn,bn->bd", h, c[:, 0])[:, None, :]     # [B,1,di]
        y = y + xc.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)
        y = y.astype(dt) * jax.nn.silu(z)
        out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(dt))
        return out, {"conv": new_conv, "ssm": h}

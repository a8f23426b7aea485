"""Shared model layers: norms, RoPE variants, MLPs, embeddings.

Conventions:
  * residual stream is `compute_dtype` (bf16); norms and softmax in fp32.
  * all learned matrices are declared via `ParamMeta` with logical axes —
    sharding is decided centrally in `repro.distributed.sharding`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.meta import ParamMeta


# --------------------------------------------------------------------------
# normalization
# --------------------------------------------------------------------------

def norm_meta(cfg, dim: Optional[int] = None):
    d = dim or cfg.d_model
    if cfg.norm == "layernorm":
        return {
            "scale": ParamMeta((d,), (None,), init="ones"),
            "bias": ParamMeta((d,), (None,), init="zeros"),
        }
    return {"scale": ParamMeta((d,), (None,), init="ones")}


def apply_norm(cfg, p, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Norm with fp32 *accumulation* but bf16 elementwise math.

    Deliberately avoids `x.astype(f32)` on the full tensor: that convert is
    the first op of every layer body, and XLA hoists it out of the
    remat/backward loop — converting the whole [L, B, S, D] saved-residual
    stack to fp32 in HBM (33.8 GB/device for llama3-405b, measured).
    Reductions accumulate in fp32 via dtype=..., which keeps the statistics
    accurate without materializing an fp32 copy of x.
    """
    dtype = x.dtype
    if cfg.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                       dtype=jnp.float32) - jnp.square(mu)
        inv = jax.lax.rsqrt(var + eps)
        y = (x - mu.astype(dtype)) * inv.astype(dtype)
        y = y * p["scale"].astype(dtype) + p["bias"].astype(dtype)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                      dtype=jnp.float32)
        y = x * jax.lax.rsqrt(ms + eps).astype(dtype) * p["scale"].astype(dtype)
    return y.astype(dtype)


def rms_norm_head(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head q/k RMSNorm (qwen3)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# rotary position embeddings (standard / partial / m-rope)
# --------------------------------------------------------------------------

def _rope_angles(positions: jax.Array, n_freq: int, theta: float) -> jax.Array:
    """positions [..., S] -> angles [..., S, n_freq] (fp32)."""
    freq = jnp.arange(n_freq, dtype=jnp.float32)
    inv = theta ** (-freq / n_freq)
    return positions.astype(jnp.float32)[..., None] * inv


def _rotate_half(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(cfg, x: jax.Array, positions: jax.Array) -> jax.Array:
    """Apply rotary embedding.

    x:         [B, S, H, Dh]
    positions: [B, S] int32, or [3, B, S] for m-rope.
    """
    if cfg.rope in ("none", "learned"):
        return x
    dh = x.shape[-1]
    if cfg.rope == "mrope":
        n_freq = dh // 2
        sections = cfg.mrope_sections
        assert sum(sections) == n_freq, (sections, n_freq)
        angle_parts = []
        start = 0
        for axis, sec in enumerate(sections):
            freq = jnp.arange(start, start + sec, dtype=jnp.float32)
            inv = cfg.rope_theta ** (-2.0 * freq / dh)
            ang = positions[axis].astype(jnp.float32)[..., None] * inv  # [B,S,sec]
            angle_parts.append(ang)
            start += sec
        angles = jnp.concatenate(angle_parts, axis=-1)  # [B, S, n_freq]
    else:
        rot = int(dh * cfg.rope_fraction)
        rot -= rot % 2
        angles = _rope_angles(positions, rot // 2, cfg.rope_theta)

    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)  # [B,S,1,n_freq]
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    rot = 2 * angles.shape[-1]
    if rot == dh:
        return _rotate_half(x, cos, sin)
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    return jnp.concatenate([_rotate_half(x_rot, cos, sin), x_pass], axis=-1)


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

def mlp_meta(cfg):
    d, f = cfg.d_model, cfg.d_ff
    if cfg.glu:
        return {
            "w_gate": ParamMeta((d, f), ("embed", "mlp")),
            "w_up": ParamMeta((d, f), ("embed", "mlp")),
            "w_down": ParamMeta((f, d), ("mlp", "embed")),
        }
    return {
        "w_up": ParamMeta((d, f), ("embed", "mlp")),
        "w_down": ParamMeta((f, d), ("mlp", "embed")),
    }


def _act(cfg, x):
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


def apply_mlp(cfg, p, x: jax.Array) -> jax.Array:
    dt = x.dtype
    with jax.named_scope("mlp"):
        if cfg.glu:
            g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
            u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
            h = _act(cfg, g) * u
        else:
            h = _act(cfg, jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt)))
        return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))


# --------------------------------------------------------------------------
# embeddings / logits
# --------------------------------------------------------------------------

def embed_meta(cfg):
    # tied tables double as the LM head: scale down so initial logits are O(1)
    scale = cfg.d_model ** -0.5 if cfg.tie_embeddings else 1.0
    m = {"in_table": ParamMeta((cfg.vocab_size, cfg.d_model),
                               ("in_vocab", "embed_tp"), scale=scale)}
    if not cfg.tie_embeddings:
        m["out_head"] = ParamMeta((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    if cfg.rope == "learned":
        m["pos_table"] = ParamMeta((cfg.source_len + cfg.max_positions, cfg.d_model),
                                   (None, "embed_tp"), scale=0.02)
    return m


def embed_tokens(cfg, p, tokens: jax.Array, positions=None) -> jax.Array:
    """Row lookup in the input table.

    Its gradient is one scatter-add of the row cotangents into a zeroed
    table of the table's dtype.  `TRAIN_RULES` never shard the table's
    vocab dim (`in_vocab`), so the lookup needs no communication.
    """
    from repro.distributed.autoshard import constrain, constrain_residual
    with jax.named_scope("embed"):
        cdt = jnp.dtype(cfg.compute_dtype)
        tokens = constrain(tokens, (None,) * tokens.ndim)
        x = jnp.take(p["in_table"], tokens, axis=0).astype(cdt)
        if cfg.rope == "learned" and positions is not None:
            positions = constrain(positions, (None,) * positions.ndim)
            pe = jnp.take(p["pos_table"], positions, axis=0)
            x = x + pe.astype(x.dtype)
        return constrain_residual(x)


def logits_head(cfg, p, x: jax.Array) -> jax.Array:
    from repro.distributed.autoshard import constrain_logits
    with jax.named_scope("logits"):
        table = p["in_table"].T if cfg.tie_embeddings else p["out_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, table.astype(x.dtype))
        return constrain_logits(logits)

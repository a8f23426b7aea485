"""Model facade: one entry point per family for meta/init/forward/serve,
plus ShapeDtypeStruct input specs for the dry-run.

Every function takes the `ModelConfig` first; family dispatch happens here
so launch/, training/ and the tracer never branch on family themselves.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import encdec, transformer
from repro.models import meta as meta_mod
from repro.models.losses import fused_next_token_loss


def n_image_patches(cfg, seq_len: int) -> int:
    """Static patch count for the VLM stub frontend."""
    return min(1024, max(1, seq_len // 4))


def model_meta(cfg: ModelConfig):
    if cfg.family == "encdec":
        return encdec.model_meta(cfg)
    return transformer.model_meta(cfg)


def init_params(cfg: ModelConfig, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    return meta_mod.materialize(model_meta(cfg), key, cfg.param_dtype)


def abstract_params(cfg: ModelConfig, dtype: str = None):
    return meta_mod.abstract(model_meta(cfg), dtype or cfg.param_dtype)


def param_logical_axes(cfg: ModelConfig):
    return meta_mod.logical_axes(model_meta(cfg))


def param_count(cfg: ModelConfig) -> int:
    return meta_mod.param_count(model_meta(cfg))


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE: top_k of num_experts experts)."""
    total = param_count(cfg)
    if not cfg.num_experts:
        return total
    expert_p = 3 * cfg.d_model * cfg.moe_d_ff * cfg.num_experts * cfg.num_layers
    active_expert_p = expert_p * cfg.top_k // cfg.num_experts
    return total - expert_p + active_expert_p


def flops_param_count(cfg: ModelConfig) -> int:
    """N for MODEL_FLOPS = 6·N·D: active matmul params per token.

    Excludes the embedding gather (0 matmul FLOPs) and learned position
    tables; includes the LM-head matmul (D x V) whether tied or not.
    """
    n = active_param_count(cfg)
    n -= cfg.vocab_size * cfg.d_model          # in_table gather
    if cfg.rope == "learned":
        n -= (cfg.source_len + cfg.max_positions) * cfg.d_model
    if cfg.tie_embeddings:
        n += cfg.vocab_size * cfg.d_model      # tied head still matmuls
    return n


# --------------------------------------------------------------------------
# steps
# --------------------------------------------------------------------------

def forward(cfg, params, batch, *, attn_impl="auto", remat="none"):
    if cfg.family == "encdec":
        return encdec.forward(cfg, params, batch, attn_impl=attn_impl,
                              remat=remat)
    return transformer.forward(cfg, params, batch, attn_impl=attn_impl,
                               remat=remat)


def loss_fn(cfg, params, batch, *, attn_impl="auto", remat="none"):
    """Training loss: fused head+xent on hidden states (no [B,S,V] logits)."""
    mod = encdec if cfg.family == "encdec" else transformer
    hidden, aux = mod.forward_hidden(cfg, params, batch, attn_impl=attn_impl,
                                     remat=remat)
    return fused_next_token_loss(cfg, params["embed"], hidden, batch, aux)


def prefill(cfg, params, batch, *, attn_impl="auto", cache_len=None):
    if cfg.family == "encdec":
        return encdec.prefill(cfg, params, batch, attn_impl=attn_impl,
                              cache_len=cache_len)
    return transformer.prefill(cfg, params, batch, attn_impl=attn_impl,
                               cache_len=cache_len)


def decode_step(cfg, params, cache, tokens, pos, *, positions=None):
    if cfg.family == "encdec":
        return encdec.decode_step(cfg, params, cache, tokens, pos)
    return transformer.decode_step(cfg, params, cache, tokens, pos,
                                   positions=positions)


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Train/prefill batch structure for (cfg, shape)."""
    B, S = shape.global_batch, shape.seq_len
    i32, f32 = jnp.int32, jnp.float32
    if cfg.family == "encdec":
        return {"frame_embeds": _sds((B, cfg.source_len, cfg.d_model), f32),
                "tokens": _sds((B, S), i32)}
    if cfg.family == "vlm":
        n_img = n_image_patches(cfg, S)
        return {"patch_embeds": _sds((B, n_img, cfg.d_model), f32),
                "tokens": _sds((B, S - n_img), i32),
                "positions": _sds((3, B, S), i32)}
    return {"tokens": _sds((B, S), i32)}


def cache_specs(cfg: ModelConfig, shape: ShapeSpec, dtype=jnp.bfloat16):
    """Decode-cache structure for (cfg, shape).

    Stacked dict {k: [L,B,Sc,K,Dh], ...} when all layers share one KV
    length (decode scans over layers — single-layer buffer liveness, fast
    compiles); per-layer list for heterogeneous windowed retention
    (gemma3/hymba at 500k) and enc-dec.
    """
    B, S = shape.global_batch, shape.seq_len
    windows = cfg.layer_windows()
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    di = cfg.expand * cfg.d_model
    Ln = cfg.num_layers

    if cfg.family != "encdec" and transformer.uniform_cache(
            cfg, shape.windowed_cache):
        entry: Dict[str, Any] = {}
        if cfg.family != "ssm":
            w = windows[0]
            sc = min(S, w) if (shape.windowed_cache and w > 0) else S
            entry["k"] = _sds((Ln, B, sc, K, Dh), dtype)
            entry["v"] = _sds((Ln, B, sc, K, Dh), dtype)
        if cfg.family in ("ssm", "hybrid"):
            entry["conv"] = _sds((Ln, B, cfg.d_conv - 1, di), jnp.float32)
            entry["ssm"] = _sds((Ln, B, di, cfg.ssm_state), jnp.float32)
        return entry

    out = []
    for li in range(Ln):
        entry = {}
        if cfg.family != "ssm":
            w = windows[li]
            sc = min(S, w) if (shape.windowed_cache and w > 0) else S
            entry["k"] = _sds((B, sc, K, Dh), dtype)
            entry["v"] = _sds((B, sc, K, Dh), dtype)
        if cfg.family in ("ssm", "hybrid"):
            entry["conv"] = _sds((B, cfg.d_conv - 1, di), jnp.float32)
            entry["ssm"] = _sds((B, di, cfg.ssm_state), jnp.float32)
        if cfg.family == "encdec":
            entry["cross_k"] = _sds((B, cfg.source_len, K, Dh), dtype)
            entry["cross_v"] = _sds((B, cfg.source_len, K, Dh), dtype)
        out.append(entry)
    return out


def decode_input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    B = shape.global_batch
    specs = {"cache": cache_specs(cfg, shape),
             "tokens": _sds((B, 1), jnp.int32),
             "pos": _sds((), jnp.int32)}
    if cfg.family == "vlm":
        specs["positions"] = _sds((3, B, 1), jnp.int32)
    return specs


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """All step inputs (minus params) for the (cfg, shape) cell."""
    if shape.kind in ("train", "prefill"):
        return {"batch": batch_specs(cfg, shape)}
    return decode_input_specs(cfg, shape)


# --------------------------------------------------------------------------
# concrete demo batches (smoke tests / examples)
# --------------------------------------------------------------------------

def demo_batch(cfg: ModelConfig, batch_size: int, seq_len: int, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    if cfg.family == "encdec":
        k1, k2 = jax.random.split(key)
        return {"frame_embeds": jax.random.normal(
                    k1, (batch_size, cfg.source_len, cfg.d_model), jnp.float32),
                "tokens": jax.random.randint(
                    k2, (batch_size, seq_len), 0, cfg.vocab_size, jnp.int32)}
    if cfg.family == "vlm":
        n_img = n_image_patches(cfg, seq_len)
        k1, k2 = jax.random.split(key)
        pos = jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32),
                               (3, batch_size, seq_len))
        return {"patch_embeds": jax.random.normal(
                    k1, (batch_size, n_img, cfg.d_model), jnp.float32),
                "tokens": jax.random.randint(
                    k2, (batch_size, seq_len - n_img), 0, cfg.vocab_size, jnp.int32),
                "positions": pos}
    return {"tokens": jax.random.randint(
        key, (batch_size, seq_len), 0, cfg.vocab_size, jnp.int32)}

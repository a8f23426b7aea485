"""Encoder-decoder transformer (whisper-tiny backbone).

The conv/audio frontend is a STUB by assignment: the model consumes
precomputed frame embeddings [B, source_len, d_model].  The encoder is a
bidirectional transformer; the decoder adds cross-attention against cached
encoder K/V.  Learned absolute positions (whisper style).
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro.distributed.autoshard import constrain_residual
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models.transformer import stack_meta, _maybe_remat, layer_params


def encoder_block_meta(cfg):
    return {"norm1": L.norm_meta(cfg), "attn": attn_mod.attention_meta(cfg),
            "norm2": L.norm_meta(cfg), "mlp": L.mlp_meta(cfg)}


def decoder_block_meta(cfg):
    return {"norm1": L.norm_meta(cfg), "attn": attn_mod.attention_meta(cfg),
            "norm2": L.norm_meta(cfg), "cross": attn_mod.attention_meta(cfg),
            "norm3": L.norm_meta(cfg), "mlp": L.mlp_meta(cfg)}


def model_meta(cfg) -> Dict[str, Any]:
    return {
        "embed": L.embed_meta(cfg),
        "enc_layers": stack_meta(encoder_block_meta(cfg), cfg.encoder_layers),
        "enc_norm": L.norm_meta(cfg),
        "layers": stack_meta(decoder_block_meta(cfg), cfg.num_layers),
        "final_norm": L.norm_meta(cfg),
    }


# --------------------------------------------------------------------------

def encode(cfg, params, frame_embeds, *, remat="none"):
    """Encoder over stub frame embeddings [B, Sm, D]."""
    with jax.named_scope("encoder"):
        x = frame_embeds.astype(jnp.dtype(cfg.compute_dtype))
        B, Sm, _ = x.shape
        pos = jnp.arange(Sm, dtype=jnp.int32)
        pe = jnp.take(params["embed"]["pos_table"], pos, axis=0)
        x = x + pe.astype(x.dtype)[None]

        def body(carry, p):
            xc = constrain_residual(carry)
            h = L.apply_norm(cfg, p["norm1"], xc)
            a = attn_mod.apply_attention(cfg, p["attn"], h, None, causal=False)
            xc = xc + a
            h2 = L.apply_norm(cfg, p["norm2"], xc)
            return constrain_residual(xc + L.apply_mlp(cfg, p["mlp"], h2)), None

        body = _maybe_remat(body, remat)
        x, _ = jax.lax.scan(body, x, params["enc_layers"])
        return L.apply_norm(cfg, params["enc_norm"], x)


def _decoder_layers(cfg, params, x, positions, memory, *, remat="none",
                    collect_cache=False):
    def body(carry, p):
        xc, aux = carry
        xc = constrain_residual(xc)
        h = L.apply_norm(cfg, p["norm1"], xc)
        q, k, v = attn_mod.project_qkv(cfg, p["attn"], h, h, None, None)
        with jax.named_scope("self_attn"):
            out = attn_mod.attend(cfg, q, k, v, causal=True)
            a = jnp.einsum("bsz,zd->bsd", out.reshape(*out.shape[:2], -1),
                           p["attn"]["wo"].astype(xc.dtype))
        xc = xc + a
        h2 = L.apply_norm(cfg, p["norm2"], xc)
        mem_kv = attn_mod.encode_memory_kv(cfg, p["cross"], memory)
        xc = xc + attn_mod.apply_cross_attention(cfg, p["cross"], h2, mem_kv)
        h3 = L.apply_norm(cfg, p["norm3"], xc)
        xc = xc + L.apply_mlp(cfg, p["mlp"], h3)
        cache = {"k": k, "v": v, "cross_k": mem_kv[0], "cross_v": mem_kv[1]} \
            if collect_cache else None
        return (xc, aux), cache

    body = _maybe_remat(body, remat)
    (x, _), caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    return x, caches


def forward_hidden(cfg, params, batch, *, attn_impl="auto", remat="none"):
    """Teacher-forced forward to decoder hidden states [B,S,D]."""
    memory = encode(cfg, params, batch["frame_embeds"], remat=remat)
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = L.embed_tokens(cfg, params["embed"], tokens,
                       positions=positions + cfg.source_len)
    x, _ = _decoder_layers(cfg, params, x, positions, memory, remat=remat)
    return L.apply_norm(cfg, params["final_norm"], x), jnp.zeros((), jnp.float32)


def forward(cfg, params, batch, *, attn_impl="auto", remat="none"):
    x, aux = forward_hidden(cfg, params, batch, attn_impl=attn_impl,
                            remat=remat)
    return L.logits_head(cfg, params["embed"], x), aux


def prefill(cfg, params, batch, *, attn_impl="auto", cache_len=None):
    memory = encode(cfg, params, batch["frame_embeds"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = L.embed_tokens(cfg, params["embed"], tokens,
                       positions=positions + cfg.source_len)
    x, caches = _decoder_layers(cfg, params, x, positions, memory,
                                collect_cache=True)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.logits_head(cfg, params["embed"], x[:, -1:])
    if cache_len is not None and S < cache_len:
        pad = [(0, 0), (0, 0), (0, cache_len - S), (0, 0), (0, 0)]
        caches = {k: (jnp.pad(v, pad) if k in ("k", "v") else v)
                  for k, v in caches.items()}
    cache_list = [jax.tree.map(lambda a: a[i], caches)
                  for i in range(cfg.num_layers)]
    return logits, cache_list


def decode_step(cfg, params, cache: List[Dict[str, jax.Array]], tokens, pos,
                *, positions=None):
    """One decoder token against self-KV + cached cross-KV."""
    B = tokens.shape[0]
    pos_ids = jnp.full((B, 1), pos, jnp.int32)
    x = L.embed_tokens(cfg, params["embed"], tokens,
                       positions=pos_ids + cfg.source_len)
    new_cache = []
    for li in range(cfg.num_layers):
        p = layer_params(params["layers"], li)
        entry = dict(cache[li])
        with jax.named_scope(f"layer_{li}"):
            h = L.apply_norm(cfg, p["norm1"], x)
            a, entry["k"], entry["v"] = attn_mod.decode_attention(
                cfg, p["attn"], h, entry["k"], entry["v"], pos)
            x = x + a
            h2 = L.apply_norm(cfg, p["norm2"], x)
            c = attn_mod.apply_cross_attention(
                cfg, p["cross"], h2, (entry["cross_k"], entry["cross_v"]))
            x = x + c
            h3 = L.apply_norm(cfg, p["norm3"], x)
            x = x + L.apply_mlp(cfg, p["mlp"], h3)
        new_cache.append(entry)
    x = L.apply_norm(cfg, params["final_norm"], x)
    return L.logits_head(cfg, params["embed"], x), new_cache

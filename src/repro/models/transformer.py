"""Decoder-LM assembly for all families: dense / moe / ssm / hybrid / vlm.

Layers run under `lax.scan` over stacked parameters (fast compiles, uniform
remat); per-layer attention windows ride along as scan inputs (gemma3's 5:1
local:global pattern, hymba's 3 global layers).  Decode is an unrolled
python loop so per-layer caches may have heterogeneous lengths (windowed
retention at 500k context).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.distributed.autoshard import constrain_residual
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.meta import ParamMeta, tree_map_meta


# --------------------------------------------------------------------------
# parameter trees
# --------------------------------------------------------------------------

def block_meta(cfg) -> Dict[str, Any]:
    fam = cfg.family
    if fam == "ssm":
        return {"norm1": L.norm_meta(cfg), "ssm": ssm_mod.ssm_meta(cfg)}
    m: Dict[str, Any] = {"norm1": L.norm_meta(cfg),
                         "attn": attn_mod.attention_meta(cfg),
                         "norm2": L.norm_meta(cfg)}
    if fam == "moe":
        m["moe"] = moe_mod.moe_meta(cfg)
    else:
        m["mlp"] = L.mlp_meta(cfg)
    if fam == "hybrid":
        m["ssm"] = ssm_mod.ssm_meta(cfg)
    if cfg.sandwich_norm:
        m["post_norm1"] = L.norm_meta(cfg)
        m["post_norm2"] = L.norm_meta(cfg)
    return m


def stack_meta(tree, n: int):
    """Prepend a stacked `layers` dim to every leaf."""
    return tree_map_meta(
        lambda _p, m: ParamMeta((n,) + m.shape, ("layers",) + m.logical,
                                init=m.init, scale=m.scale, dtype=m.dtype),
        tree)


def model_meta(cfg) -> Dict[str, Any]:
    m = {"embed": L.embed_meta(cfg),
         "layers": stack_meta(block_meta(cfg), cfg.num_layers),
         "final_norm": L.norm_meta(cfg)}
    return m


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def apply_block(cfg, p, x, positions, window, *, attn_impl="auto",
                collect_cache=False):
    """One layer. Returns (x, aux, cache_entry_or_None)."""
    fam = cfg.family
    aux = jnp.zeros((), jnp.float32)
    cache: Dict[str, jax.Array] = {}

    if fam == "ssm":
        h = L.apply_norm(cfg, p["norm1"], x)
        if collect_cache:
            y, st = _ssm_with_state(cfg, p["ssm"], h)
            cache.update(st)
        else:
            y = ssm_mod.apply_ssm(cfg, p["ssm"], h)
        return x + y, aux, cache or None

    h = L.apply_norm(cfg, p["norm1"], x)
    q, k, v = attn_mod.project_qkv(cfg, p["attn"], h, h, positions, positions)
    with jax.named_scope("attn"):
        out = attn_mod.attend(cfg, q, k, v, causal=True, window=window,
                              impl=attn_impl)
        attn_out = jnp.einsum("bsz,zd->bsd", out.reshape(*out.shape[:2], -1),
                              p["attn"]["wo"].astype(x.dtype))
    if collect_cache:
        cache["k"], cache["v"] = k, v

    if fam == "hybrid":
        if collect_cache:
            ssm_out, st = _ssm_with_state(cfg, p["ssm"], h)
            cache.update(st)
        else:
            ssm_out = ssm_mod.apply_ssm(cfg, p["ssm"], h)
        attn_out = 0.5 * (attn_out + ssm_out)   # parallel heads, mean-fused

    if cfg.sandwich_norm:
        attn_out = L.apply_norm(cfg, p["post_norm1"], attn_out)
    x = x + attn_out

    h2 = L.apply_norm(cfg, p["norm2"], x)
    if fam == "moe":
        ff, aux = moe_mod.apply_moe(cfg, p["moe"], h2)
    else:
        ff = L.apply_mlp(cfg, p["mlp"], h2)
    if cfg.sandwich_norm:
        ff = L.apply_norm(cfg, p["post_norm2"], ff)
    return x + ff, aux, cache or None


def _ssm_with_state(cfg, p, h):
    """Full-seq SSM that also returns the terminal (conv, ssm) state."""
    y = ssm_mod.apply_ssm(cfg, p, h)
    # terminal states, recomputed cheaply:
    dt = h.dtype
    xz = jnp.einsum("bsd,de->bse", h, p["in_proj"].astype(dt))
    x_in, _ = jnp.split(xz, 2, axis=-1)
    conv_state = x_in[:, -(cfg.d_conv - 1):, :].astype(jnp.float32)
    # ssm terminal state via a cheap rerun of the chunked scan
    xc = jax.nn.silu(ssm_mod._conv1d_causal(cfg, p, x_in))
    a_bar, bx, _c = ssm_mod._ssm_inputs(cfg, p, xc, cfg.d_model)
    def step(hc, t):
        a_t, b_t = t
        return a_t * hc + b_t, None
    B = h.shape[0]
    di = cfg.expand * cfg.d_model
    h0 = jnp.zeros((B, di, cfg.ssm_state), jnp.float32)
    h_last, _ = jax.lax.scan(step, h0, (a_bar.transpose(1, 0, 2, 3),
                                        bx.transpose(1, 0, 2, 3)))
    return y, {"conv": conv_state, "ssm": h_last}


# --------------------------------------------------------------------------
# full forward (train / prefill)
# --------------------------------------------------------------------------

REMAT_POLICIES = {
    "none": None,
    "dots": "dots_with_no_batch_dims_saveable",
    "full": "nothing_saveable",
}


def _maybe_remat(fn, policy: str):
    if policy == "none":
        return fn
    pol = getattr(jax.checkpoint_policies, REMAT_POLICIES[policy])
    return jax.checkpoint(fn, policy=pol)


def apply_layers(cfg, stacked, x, positions, *, attn_impl="auto",
                 remat="none", collect_cache=False):
    """Scan over stacked layer params. Returns (x, aux_sum, stacked_cache)."""
    windows = jnp.asarray(cfg.layer_windows(), jnp.int32)

    def body(carry, layer_in):
        xc, aux = carry
        p, w = layer_in
        with jax.named_scope("layer"):
            xc = constrain_residual(xc)
            xn, a, cache = apply_block(cfg, p, xc, positions, w,
                                       attn_impl=attn_impl,
                                       collect_cache=collect_cache)
            xn = constrain_residual(xn)
        return (xn, aux + a), cache

    body = _maybe_remat(body, remat)
    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    (stacked, windows))
    return x, aux, caches


def forward_hidden(cfg, params, batch, *, attn_impl="auto", remat="none"):
    """Forward to final-norm hidden states [B,S,D]. Returns (hidden, aux)."""
    x, positions = embed_inputs(cfg, params, batch)
    x, aux, _ = apply_layers(cfg, params["layers"], x, positions,
                             attn_impl=attn_impl, remat=remat)
    return L.apply_norm(cfg, params["final_norm"], x), aux


def forward(cfg, params, batch, *, attn_impl="auto", remat="none"):
    """Full forward to logits. batch is a dict (family-specific).

    Returns (logits [B,S,V], aux_loss).
    """
    x, aux = forward_hidden(cfg, params, batch, attn_impl=attn_impl,
                            remat=remat)
    logits = L.logits_head(cfg, params["embed"], x)
    return logits, aux


def embed_inputs(cfg, params, batch):
    """Family-specific input embedding. Returns (x [B,S,D], positions)."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(jnp.dtype(cfg.compute_dtype))
        tok_x = L.embed_tokens(cfg, params["embed"], tokens)
        with jax.named_scope("vision_stub"):
            x = jnp.concatenate([patches, tok_x], axis=1)
        positions = batch["positions"]          # [3, B, S] m-rope ids
        return x, positions
    S = tokens.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = L.embed_tokens(cfg, params["embed"], tokens, positions=positions)
    return x, positions


# --------------------------------------------------------------------------
# decode (unrolled layers; heterogeneous per-layer caches)
# --------------------------------------------------------------------------

def layer_params(stacked, i: int):
    return jax.tree.map(lambda a: a[i], stacked)


def uniform_cache(cfg, windowed: bool) -> bool:
    """True when all layers share one KV length (stacked+scanned decode)."""
    if cfg.family == "ssm":
        return True
    if not windowed:
        return True
    ws = set(cfg.layer_windows())
    return len(ws) == 1


def init_cache(cfg, batch_size: int, seq_len: int, *, windowed: bool,
               dtype=jnp.bfloat16):
    """Decode cache: stacked dict {k: [L,B,Sc,K,Dh], ...} when all layers
    share a KV length (scanned decode, single-layer buffer liveness), else
    a per-layer list (heterogeneous windowed retention at 500k ctx)."""
    windows = cfg.layer_windows()
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    Ln = cfg.num_layers
    if uniform_cache(cfg, windowed):
        entry: Dict[str, jax.Array] = {}
        if cfg.family != "ssm":
            w = windows[0]
            sc = min(seq_len, w) if (windowed and w > 0) else seq_len
            entry["k"] = jnp.zeros((Ln, batch_size, sc, K, Dh), dtype)
            entry["v"] = jnp.zeros((Ln, batch_size, sc, K, Dh), dtype)
        if cfg.family in ("ssm", "hybrid"):
            st = ssm_mod.init_ssm_state(cfg, batch_size)
            entry["conv"] = jnp.broadcast_to(st["conv"][None],
                                             (Ln,) + st["conv"].shape).copy()
            entry["ssm"] = jnp.broadcast_to(st["ssm"][None],
                                            (Ln,) + st["ssm"].shape).copy()
        return entry
    caches = []
    for li in range(Ln):
        entry = {}
        if cfg.family != "ssm":
            w = windows[li]
            sc = min(seq_len, w) if (windowed and w > 0) else seq_len
            entry["k"] = jnp.zeros((batch_size, sc, K, Dh), dtype)
            entry["v"] = jnp.zeros((batch_size, sc, K, Dh), dtype)
        if cfg.family in ("ssm", "hybrid"):
            st = ssm_mod.init_ssm_state(cfg, batch_size)
            entry["conv"], entry["ssm"] = st["conv"], st["ssm"]
        caches.append(entry)
    return caches


def decode_step(cfg, params, cache, tokens, pos, *, positions=None):
    """One decode step. tokens [B,1] -> (logits [B,1,V], new_cache).

    `cache` is either a stacked dict (scanned layers — one layer's buffers
    live at a time, fast compiles) or a per-layer list (unrolled —
    heterogeneous cache lengths).  `pos` scalar int32; `positions`
    overrides rope ids (m-rope [3,B,1]).
    """
    B = tokens.shape[0]
    if positions is None:
        positions = jnp.full((B, 1), pos, jnp.int32)
    if cfg.rope == "learned":
        x = L.embed_tokens(cfg, params["embed"], tokens,
                           positions=positions + cfg.source_len)
    else:
        x = L.embed_tokens(cfg, params["embed"], tokens)

    if isinstance(cache, dict):
        x, new_cache = _decode_scan(cfg, params, cache, x, pos, positions)
    else:
        windows = cfg.layer_windows()
        new_cache = []
        for li in range(cfg.num_layers):
            p = layer_params(params["layers"], li)
            entry = dict(cache[li])
            with jax.named_scope(f"layer_{li}"):
                x = constrain_residual(x)
                x, entry = _decode_block(cfg, p, x, entry, pos, windows[li],
                                         positions)
            new_cache.append(entry)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.logits_head(cfg, params["embed"], x)
    return logits, new_cache


def _decode_scan(cfg, params, cache, x, pos, positions):
    """Scanned decode over stacked per-layer cache (uniform KV length)."""
    windows_static = cfg.layer_windows()
    windows = jnp.asarray(windows_static, jnp.int32)
    sc = cache["k"].shape[2] if "k" in cache else 0
    # static: cache allocated at exactly the (uniform) window size
    windowed = (cfg.family != "ssm" and len(set(windows_static)) == 1
                and windows_static[0] > 0 and sc == windows_static[0])

    def body(carry, layer_in):
        xc = carry
        p, entry, w = layer_in
        with jax.named_scope("layer"):
            xc = constrain_residual(xc)
            xn, entry = _decode_block(cfg, p, xc, dict(entry), pos, w,
                                      positions, windowed_static=windowed)
        return xn, entry

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache, windows))
    return x, new_cache


def _decode_block(cfg, p, x, entry, pos, window, positions,
                  windowed_static=None):
    fam = cfg.family
    if fam == "ssm":
        h = L.apply_norm(cfg, p["norm1"], x)
        y, st = ssm_mod.decode_ssm(cfg, p["ssm"], h,
                                   {"conv": entry["conv"], "ssm": entry["ssm"]})
        entry.update(st)
        return x + y, entry

    h = L.apply_norm(cfg, p["norm1"], x)
    # windowed retention: the cache was allocated at exactly the window size
    if windowed_static is None:
        windowed_static = window > 0 and entry["k"].shape[1] == window
    attn_out, entry["k"], entry["v"] = attn_mod.decode_attention(
        cfg, p["attn"], h, entry["k"], entry["v"], pos,
        window=window, windowed_cache=windowed_static, positions=positions)
    if fam == "hybrid":
        y, st = ssm_mod.decode_ssm(cfg, p["ssm"], h,
                                   {"conv": entry["conv"], "ssm": entry["ssm"]})
        entry.update(st)
        attn_out = 0.5 * (attn_out + y)
    if cfg.sandwich_norm:
        attn_out = L.apply_norm(cfg, p["post_norm1"], attn_out)
    x = x + attn_out
    h2 = L.apply_norm(cfg, p["norm2"], x)
    if fam == "moe":
        ff, _ = moe_mod.apply_moe(cfg, p["moe"], h2)
    else:
        ff = L.apply_mlp(cfg, p["mlp"], h2)
    if cfg.sandwich_norm:
        ff = L.apply_norm(cfg, p["post_norm2"], ff)
    return x + ff, entry


# --------------------------------------------------------------------------
# prefill
# --------------------------------------------------------------------------

def prefill(cfg, params, batch, *, attn_impl="auto", cache_len=None):
    """Process a prompt; return (logits_last [B,1,V], cache list).

    `cache_len` reserves headroom for subsequent decode steps (the KV cache
    is padded with zeros past the prompt; decode masks by position).
    """
    x, positions = embed_inputs(cfg, params, batch)
    x, _aux, caches = apply_layers(cfg, params["layers"], x, positions,
                                   attn_impl=attn_impl, collect_cache=True)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.logits_head(cfg, params["embed"], x[:, -1:])
    caches = _pad_kv(caches, cache_len)
    # stacked cache dict {k: [L,B,Sc,K,Dh], ...} — decode scans over layers
    return logits, caches


def _pad_kv(caches, cache_len):
    if cache_len is None:
        return caches
    def pad_one(name, a):
        if name in ("k", "v") and a.shape[2] < cache_len:
            padw = [(0, 0)] * a.ndim
            padw[2] = (0, cache_len - a.shape[2])
            return jnp.pad(a, padw)
        return a
    return {k: pad_one(k, v) for k, v in caches.items()}

"""Dense decoder: GQA attention, gated MLP, RMSNorm, untied LM head.

Per layer x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x)) and
x += Wd·(silu(Wg·n2(x)) * Wu·n2(x)) with n = RMSNorm; causal GQA
attention, keys further back than `window` masked when it is > 0; rope on
the first `rope_fraction` of each head (none where `rope` is "none" or
"learned").  No auxiliary loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from yardstick import flops

LAYER_LEAVES = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/w_gate",
                "mlp/w_up", "mlp/w_down", "norm1/scale", "norm2/scale")


def layout(cfg: dict) -> dict:
    """{leaf path: (shape, init)} with init "normal", "table" or "ones"."""
    if (cfg.get("norm", "rmsnorm") != "rmsnorm"
            or not cfg.get("glu", True) or cfg.get("qk_norm", False)
            or cfg.get("tie_embeddings", False)
            or cfg.get("sandwich_norm", False)):
        raise ValueError(f"no dense weight layout for config {cfg['name']!r}")
    L, D, V = cfg["num_layers"], cfg["d_model"], cfg["vocab_size"]
    Q = cfg["num_heads"] * cfg["head_dim"]
    KV = cfg["num_kv_heads"] * cfg["head_dim"]
    F = cfg["d_ff"]
    return {
        "embed/in_table": ((V, D), "table"),
        "embed/out_head": ((D, V), "normal"),
        "final_norm/scale": ((D,), "ones"),
        "layers/attn/wq": ((L, D, Q), "normal"),
        "layers/attn/wk": ((L, D, KV), "normal"),
        "layers/attn/wv": ((L, D, KV), "normal"),
        "layers/attn/wo": ((L, Q, D), "normal"),
        "layers/mlp/w_gate": ((L, D, F), "normal"),
        "layers/mlp/w_up": ((L, D, F), "normal"),
        "layers/mlp/w_down": ((L, F, D), "normal"),
        "layers/norm1/scale": ((L, D), "ones"),
        "layers/norm2/scale": ((L, D), "ones"),
    }


def layer_leaves(cfg: dict) -> tuple:
    return LAYER_LEAVES


def windows(cfg: dict) -> list:
    """Each layer's attention window (0: every earlier key)."""
    return [cfg.get("window", 0)] * cfg["num_layers"]


def make_layer(cfg: dict, ops, windows=windows):
    """The dense block; `windows(cfg)` gives each layer's window."""
    H, K, Dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    theta = cfg.get("rope_theta", 10_000.0)
    fraction = cfg.get("rope_fraction", 1.0)
    use_rope = cfg.get("rope", "standard") not in ("none", "learned")
    per_layer = windows(cfg)
    mm, norm = ops.mm, ops.norm

    def rope(x, pos):
        return ops.rope(x, pos, theta, fraction) if use_rope else x

    def layer(p, x, l):
        S = x.shape[0]
        pos = jnp.arange(S, dtype=jnp.float32)
        h = norm(x, p["norm1/scale"])
        q = mm("sd,dz->sz", h, p["attn/wq"]).reshape(S, H, Dh)
        k = mm("sd,dz->sz", h, p["attn/wk"]).reshape(S, K, Dh)
        v = mm("sd,dz->sz", h, p["attn/wv"]).reshape(S, K, Dh)
        att = ops.attention(rope(q, pos), rope(k, pos), v, per_layer[l])
        x = x + mm("sz,zd->sd", att, p["attn/wo"])
        h = norm(x, p["norm2/scale"])
        ff = jax.nn.silu(mm("sd,df->sf", h, p["mlp/w_gate"])) \
            * mm("sd,df->sf", h, p["mlp/w_up"])
        return x + mm("sf,fd->sd", ff, p["mlp/w_down"]), jnp.zeros((), jnp.float32)

    return layer


def matmul_weights(cfg: dict) -> int:
    D, F, V = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    Q = cfg["num_heads"] * cfg["head_dim"]
    KV = cfg["num_kv_heads"] * cfg["head_dim"]
    per_layer = D * Q + 2 * D * KV + Q * D + 3 * D * F
    return cfg["num_layers"] * per_layer + D * V


def matmul_flops_per_token(cfg: dict) -> int:
    return 2 * matmul_weights(cfg)


def attention_keys(cfg: dict, seq: int) -> list:
    return [flops.mean_keys(seq, w) for w in windows(cfg)]


def tiny(cfg: dict) -> dict:
    return dict(cfg, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                head_dim=16, d_ff=96, vocab_size=512)

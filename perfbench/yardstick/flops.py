"""Model FLOPs of a training step, from the configuration's shapes.

Per token, forward: 2 FLOPs per weight of every matrix product, as the
architecture module counts them (`matmul_flops_per_token`; an embedding
is a lookup and counts nothing), plus attention's two products, QK^T and
PV, at 2 * head_dim FLOPs per query head per key the token may attend to
in each layer (`attention_keys`).  Causal attention counts only the keys
at or before the query, so a sequence of S tokens averages (S + 1) / 2
keys; a window of W caps each query's keys at W.  Training is forward
plus backward, 3x the forward.
Recomputation under remat counts nothing: it is work the model does not
need.  The chip's peak comes from `peaks.json`, keyed by device kind.
"""
from __future__ import annotations

import json
from pathlib import Path

from yardstick import arch

PEAKS = json.loads((Path(__file__).with_name("peaks.json")).read_text())


def mean_keys(seq: int, window: int = 0) -> float:
    """Mean number of keys a query attends to, causal, optional window."""
    if window and window < seq:
        full = window * (window + 1) // 2           # queries 0..W-1
        return (full + (seq - window) * window) / seq
    return (seq + 1) / 2


def train_flops_per_token(cfg: dict, seq: int) -> float:
    mod = arch.module(cfg)
    attn = 2 * 2 * cfg["num_heads"] * cfg["head_dim"] \
        * sum(mod.attention_keys(cfg, seq))
    fwd = mod.matmul_flops_per_token(cfg) + attn
    return 3.0 * fwd


def peak_flops(device_kind: str) -> float:
    try:
        return float(PEAKS[device_kind]["bf16_flops_per_s"])
    except KeyError:
        raise ValueError(f"no peak for device kind {device_kind!r}: add it "
                         f"to perfbench/yardstick/peaks.json") from None

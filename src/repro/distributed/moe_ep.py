"""Sort-based expert-parallel MoE dispatch (beyond-paper optimization).

The baseline GShard-style dispatch builds [G, Sg, E, C] one-hot tensors and
pays ~Sg^2-scaled einsum FLOPs for dispatch+combine.  This path instead:

  * runs per data-shard under shard_map (tokens stay local),
  * top-k routes, sorts token-slots by expert id, applies a global capacity,
  * scatters tokens into each *local* expert's [E_loc, C, D] buffer
    (experts sharded over the `model` axis: each shard computes its E/TP
    experts on its replicated token set — no all-to-all needed on this
    mesh layout; the only collective is the same [T, D] psum over `model`
    the einsum path pays for combine),
  * gathers + weight-combines with a scatter-add.

Dispatch/combine become O(T·k) gather/scatter instead of O(T·E·C) einsums.
Requires E % model_axis == 0 (qwen3: 128/16; mixtral's 8 experts fall back
to the einsum path, which expert-TPs them instead).

Capacity semantics differ slightly from the grouped baseline (global per
shard vs per routing group); with a no-drop capacity factor the two paths
agree numerically (tests/test_moe_ep.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _moe_shard(x_loc, router_w, wg, wu, wd, *, cfg, e_loc: int):
    """Per-(data x model)-shard MoE. x_loc [b,S,D] (replicated over model);
    wg/wu/wd hold this model-shard's E_loc experts."""
    B, S, D = x_loc.shape
    dt = x_loc.dtype
    k = cfg.top_k
    E = cfg.num_experts
    T = B * S
    xf = x_loc.reshape(T, D)
    m_idx = jax.lax.axis_index("model")

    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)                 # [T,k]
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        # aux load-balance loss (Switch; same normalization as the einsum
        # path: ce sums to k over experts)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / T
        aux = E * jnp.sum(me * ce)

    with jax.named_scope("dispatch"):
        cap = max(1, math.ceil(k * T * cfg.capacity_factor / E))
        flat_e = idx.reshape(-1)                             # [T*k]
        order = jnp.argsort(flat_e, stable=True)
        e_sorted = flat_e[order]
        tok_sorted = order // k
        gate_sorted = gates.reshape(-1)[order]
        # position within each expert's run of the sorted array
        first = jnp.searchsorted(e_sorted, e_sorted, side="left")
        pos = jnp.arange(T * k, dtype=jnp.int32) - first.astype(jnp.int32)
        keep = pos < cap
        lo = m_idx * e_loc
        local = keep & (e_sorted >= lo) & (e_sorted < lo + e_loc)
        dump = e_loc * cap                                   # overflow row
        dest = jnp.where(local, (e_sorted - lo) * cap + pos, dump)
        vals = jnp.where(local[:, None], xf[tok_sorted], 0).astype(dt)
        buf = jnp.zeros((e_loc * cap + 1, D), dt).at[dest].add(vals)
        x_e = buf[:e_loc * cap].reshape(e_loc, cap, D)

    with jax.named_scope("experts"):
        g = jnp.einsum("ecd,edf->ecf", x_e, wg.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", x_e, wu.astype(dt))
        y_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dt))

    with jax.named_scope("combine"):
        flat_y = jnp.concatenate(
            [y_e.reshape(e_loc * cap, D), jnp.zeros((1, D), dt)], axis=0)
        y_slot = flat_y[dest] * gate_sorted[:, None].astype(dt)
        y_tok = jnp.zeros((T, D), jnp.float32).at[tok_sorted].add(
            jnp.where(local[:, None], y_slot, 0).astype(jnp.float32))
        y = jax.lax.psum(y_tok, "model").astype(dt)
    # aux is identical on every model shard (router is replicated)
    return y.reshape(B, S, D), aux


def apply_moe_sort(cfg, p, x, mesh):
    """shard_map-wrapped sort-based MoE. Requires E % model == 0."""
    model_size = dict(zip(mesh.axis_names,
                          jnp.shape(mesh.devices))).get("model", 1)
    assert cfg.num_experts % model_size == 0, (cfg.num_experts, model_size)
    e_loc = cfg.num_experts // model_size
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = data_axes[0] if len(data_axes) == 1 else data_axes

    fn = functools.partial(_moe_shard, cfg=cfg, e_loc=e_loc)
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, None, None),        # x: tokens over data
                  P(None, None),               # router replicated
                  P("model", None, None),      # experts over model
                  P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False)
    y, aux = mapped(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux

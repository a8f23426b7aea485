"""Weights made from the seed, on the device, in one jitted call.

The layout is the architecture module's (`arch/<arch>.py`), with layers
stacked on a leading axis, leaf for leaf the tree the trainer takes;
`drivers.train` checks the two agree.  The seed enters as traced key
data, so one compiled program serves every seed.

Each leaf is `normal * fan_in**-0.5` (fan-in the second-last axis), the
input table `normal`, norm scales ones: initial logits are O(1) and the
first loss sits near ln(vocab) + 1/2.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from yardstick import arch


def layout(cfg: dict) -> dict:
    """{leaf path: (shape, init)} with init "normal", "table" or "ones"."""
    return arch.module(cfg).layout(cfg)


def key_data(seed: int) -> np.ndarray:
    """Threefry key words of any whole-number seed (no 32-bit overflow)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def leaf(kd, index: int, shape, init: str, dtype=jnp.float32):
    """One leaf: traced key data, static leaf index, shape and init."""
    if init == "ones":
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(jax.random.wrap_key_data(kd), index)
    x = jax.random.normal(key, shape, jnp.float32)
    if init == "normal":
        x = x * (shape[-2] if len(shape) >= 2 else shape[-1]) ** -0.5
    return x.astype(dtype)


def flat(cfg: dict, kd):
    """{path: array} for traced key data `kd` (call under jit)."""
    return {path: leaf(kd, i, shape, init)
            for i, (path, (shape, init)) in enumerate(sorted(layout(cfg).items()))}


def nest(flat_tree: dict) -> dict:
    """{"a/b": x} -> {"a": {"b": x}}."""
    out: dict = {}
    for path, x in flat_tree.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = x
    return out


def make_params(cfg: dict, seed: int, shardings=None):
    """The nested param tree of `seed`, made on the device in one call.

    `shardings` is the trainer's param sharding tree (or one sharding).
    """
    fn = jax.jit(lambda kd: nest(flat(cfg, kd)), out_shardings=shardings)
    return fn(jnp.asarray(key_data(seed)))
